"""Tile planner: map stencil geometry onto TPU register/VMEM tiling.

Counterpart of the reference's vector-folding planner
(``src/compiler/lib/Vec.*``): where YASK chooses an N-D SIMD fold (e.g.
4×4 for 16 lanes) to maximize in-register reuse between neighboring
stencil reads, the TPU equivalent chooses which dims ride the VREG
(sublane, lane) axes and what Pallas block shape to use:

* the minor-most dim is the 128-lane axis and stays whole in each tile;
* the next-to-minor dim maps to sublanes — blocks should be multiples of
  the dtype's sublane count (8 for f32, 16 for bf16);
* remaining leading dims get small blocks sized to fit the VMEM budget
  given the fused halo (radius × fuse_steps).

User fold hints (``yc_solution.set_fold_len``, the reference's ``-fold``)
override the defaults per dim; the auto-tuner searches around the plan.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from yask_tpu.backend import get_capability

#: default planning budget for direct/test calls (the runtime passes the
#: platform's own default via ``default_vmem_budget``)
_INTERPRET_PLAN_BUDGET = get_capability("cpu:interpret").plan_budget_bytes()

#: copies of a tile the build may hold at once: it double-buffers the
#: input tiles and parity-doubles the output staging when both fit the
#: budget, so blocks grow only while two copies of the once-counted
#: tiles (as the build counts them: ``BlockPrice``) do.  A fact of the
#: build's pipelining, not a guess at live values (those are the
#: capability table's ``vmem_live``, which the build tests every
#: candidate against).
_PIPELINE_COPIES = 2


def sublane_count(dtype) -> int:
    """Sublane fold unit for ``dtype`` (8 for f32, 16 for bf16) — read
    from the backend capability table (single source with VarGeom's
    alignment and the checker's models)."""
    return get_capability().sublane_count(dtype)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m if m > 1 else x


class TilePlan:
    """Explicit dataflow plan for one fused K-group's tiling.

    THE single margin-math source for the pallas path (TileLoom-style:
    emit the per-tile read/write/carry sets, derive every decision from
    them).  A plan is built per (program, fuse_steps) with the resolved
    per-dim tiling choice — ``"uniform"`` symmetric shrink, or
    ``"skew"`` streaming wavefront (the stream dim alone can take it) —
    and answers:

    * :meth:`margins` — per-dim (left, right) fetch margins of a tile
      (what the build's mL/mR and the DMA slabs use);
    * :meth:`min_block` / :meth:`margin_override` — the
      :func:`plan_blocks` hints (the skew carry's floor, the skewed
      dim's margin model);
    * :meth:`halo` — the uniform fused halo radius×K (the overlap
      core/shell split's shrink margin);
    * :meth:`dataflow` — per-sub-step read/write/carry interval sets
      for one tile, and :meth:`stage_flow`, the same per analysis stage
      (the equivalence tests consume these).

    ``e_sk`` is the per-dim skew extra width (E_sk) map; the builder
    passes :func:`~yask_tpu.ops.pallas_stencil.skew_extra_widths` so
    there is exactly one E_sk definition.
    """

    def __init__(self, program, fuse_steps: int, skew_dims=(),
                 e_sk: Optional[Dict[str, int]] = None):
        self.program = program
        ana = program.ana
        self.dims = ana.domain_dims
        self.lead = self.dims[:-1]
        self.minor = self.dims[-1]
        self.K = fuse_steps
        rad = ana.fused_step_radius()
        self.rad = {d: rad.get(d, 0) for d in self.lead}
        self.skew_dims = list(skew_dims)
        self.e_sk = dict(e_sk or {})
        self.mode = {d: "skew" if d in self.skew_dims else "uniform"
                     for d in self.lead}
        # ring depth read back through the chain (skew carry sizing)
        ring_reads = set()
        for sr in program.stage_reads:
            ring_reads.update(sr.keys())
        self.carry_depth = max(
            (g.num_slots for n, g in program.geoms.items()
             if g.is_written and not g.is_scratch and n in ring_reads),
            default=0)

    # -- geometry primitives ------------------------------------------

    def halo(self, d: str) -> int:
        """Uniform fused halo radius×K — the single definition the
        overlap core/shell split and the uniform margins share."""
        return self.rad[d] * self.K

    def margins(self) -> Tuple[Dict[str, int], Dict[str, int]]:
        """Per-dim (mL, mR) fetch margins."""
        mL, mR = {}, {}
        for d in self.lead:
            if self.mode[d] == "skew":
                mL[d] = self.halo(d)
                mR[d] = self.rad[d] + self.e_sk.get(d, 0)
            else:
                mL[d] = mR[d] = self.halo(d)
        return mL, mR

    def write_shift(self, d: str, lvl: int) -> int:
        """Skew: level ``lvl``'s write window slides left by this."""
        return (lvl - 1) * self.rad[d] if self.mode[d] == "skew" else 0

    # -- planner hints -------------------------------------------------

    def min_block(self) -> Optional[Dict[str, int]]:
        """Per-dim block floors: skew carries save (ring+1)·r-wide
        strips from the tile's own valid span."""
        out = {}
        for d in self.skew_dims:
            if self.carry_depth:
                out[d] = (self.carry_depth + 1) * self.rad[d]
        return out or None

    def margin_override(self) -> Optional[Dict[str, int]]:
        """Per-dim TOTAL modeled tile margin for :func:`plan_blocks`
        where the skew fetches less than the uniform 2·K·r."""
        out = {}
        for d in self.skew_dims:
            out[d] = (self.K + 1) * self.rad[d] + self.e_sk.get(d, 0)
        return out or None

    # -- dataflow ------------------------------------------------------

    def dataflow(self, block: Dict[str, int]) -> List[Dict]:
        """Per-sub-step interval sets of one interior tile, in
        tile-origin-relative coordinates (tile spans
        ``[0, mL + block + mR)`` per dim).  Each entry: ``{"level",
        "read": {d: (lo, hi)}, "write": {d: (lo, hi)}, "carry":
        {d: width}}``.  The write interval is the level's output
        window (shifted in a skewed dim); the read interval is the
        region the sub-step consumes.  The equivalence tests assert
        nesting (every read ⊆ the previous level's write ∪ margins)."""
        mL, mR = self.margins()
        steps = []
        for k in range(self.K):
            lvl = k + 1
            entry = {"level": lvl, "read": {}, "write": {}, "carry": {}}
            for d in self.lead:
                B, r = block[d], self.rad[d]
                if self.mode[d] == "skew":
                    lo = mL[d] - lvl * r
                    hi = lo + B + 2 * r + self.e_sk.get(d, 0)
                    wlo = mL[d] - self.write_shift(d, lvl)
                    entry["carry"][d] = (self.carry_depth + 1) * r
                    entry["write"][d] = (wlo, wlo + B)
                else:
                    lo = mL[d] - (self.K - lvl) * r - r
                    lo = max(lo, 0)
                    hi = mL[d] + B + (self.K - lvl) * r + r
                    hi = min(hi, mL[d] + B + mR[d])
                    wlo = mL[d] - (self.K - lvl) * r
                    entry["write"][d] = (wlo, mL[d] + B
                                         + (self.K - lvl) * r)
                entry["read"][d] = (lo, hi)
            steps.append(entry)
        return steps

    # -- multi-stage dataflow (cross-solution pipeline fusion) ---------

    def stage_widths(self) -> List[Dict[str, int]]:
        """Per ANALYSIS stage, per lead dim: the max one-side ghost
        width that stage's reads consume — the per-stage slices of the
        fused radius, straight off ``program.stage_reads`` (the same
        ``stage_read_widths`` definition every other margin consumer
        uses).  For a merged producer→consumer chain (every stage
        reads the one before it) the per-dim sum over stages equals
        ``self.rad`` (``fused_step_radius``, the longest chain of
        dependent stages) and the inter-stage halo margins are exactly
        these widths, one slice per stage; stages that are not a chain
        sum to more than the step reaches."""
        out = []
        for reads in self.program.stage_reads:
            w = {d: 0 for d in self.lead}
            for vv in reads.values():
                for d, (l, r) in vv.items():
                    if d in w:
                        w[d] = max(w[d], l, r)
            out.append(w)
        return out

    def stage_flow(self, block: Dict[str, int]) -> List[Dict]:
        """Per sub-step level, per analysis stage: the stage's write
        and read intervals of one tile (tile-origin-relative, lead
        dims).  The FINAL stage writes the level's output window
        (:meth:`dataflow`'s ``write``); each upstream stage's window is
        expanded per side by the downstream tail (the sum of later
        stages' :meth:`stage_widths`) — consumer stages evaluate
        in-tile over write-halo-expanded producer windows, the
        scratch-var chain rule generalized to whole fused solutions.
        Nesting invariant: stage ``si``'s read interval equals stage
        ``si−1``'s write interval (each stage produces exactly what
        the next consumes)."""
        sw = self.stage_widths()
        tails: List[Dict[str, int]] = []
        acc = {d: 0 for d in self.lead}
        for w in reversed(sw):
            tails.append(dict(acc))
            acc = {d: acc[d] + w[d] for d in self.lead}
        tails.reverse()
        flow = []
        for entry in self.dataflow(block):
            stages = []
            for si, w in enumerate(sw):
                wr, rd = {}, {}
                for d in self.lead:
                    lo, hi = entry["write"][d]
                    t = tails[si][d]
                    wr[d] = (lo - t, hi + t)
                    rd[d] = (lo - t - w[d], hi + t + w[d])
                stages.append({"stage": si, "write": wr, "read": rd})
            flow.append({"level": entry["level"], "stages": stages})
        return flow


class BlockPrice(NamedTuple):
    """What one candidate block costs, by the build's own accounting
    (``build_pallas_chunk``'s ``_tile_bytes`` and the regions its kernel
    evaluates): the input tiles once, the work tiles (one result tile a
    written var, in-tile scratch, skew carry, pushed rings), one result
    tile, and the estimated vector instructions.  ``declared``: the
    class's ``vmem_live`` row was read off the strip kernel, and the
    work tiles are the buffers that kernel declares."""

    in_bytes: int
    work_bytes: int
    result_bytes: int
    vinstr: int
    declared: bool = False


def _balanced(extent: int, b: int, unit: int = 1) -> int:
    """The smallest block (a multiple of ``unit``) that covers
    ``extent`` in as many tiles as ``b`` does: ``ceil(extent /
    ceil(extent / b))``, so the last tile walks the fewest rows past
    the edge.  Never above ``b`` rounded down to ``unit``."""
    b = max(unit, min(b, extent) // unit * unit)
    return min(b, _ceil_to(-(-extent // -(-extent // b)), unit))


def first_block(extent: int, guess: int, unit: int = 1) -> int:
    """The first guess, snapped DOWN to a divisor of ``extent`` where
    one lies within a factor of two of it (every extent that is a
    multiple of 64 keeps its 8), else the guess itself, balanced: 801 =
    3^2 x 89 starts at 8 in 101 tiles, not at its divisor 3."""
    guess = max(1, min(guess, extent))
    b = next(c for c in range(guess, 0, -1) if extent % c == 0)
    return b if 2 * b >= guess else _balanced(extent, guess, unit)


def floor_block(extent: int, floor: int, unit: int = 1) -> int:
    """A block floor, snapped UP to a divisor of ``extent`` where one
    lies within a factor of two of it (640's carry floor of 24 stays
    32, which the plans that run rest on), else the floor itself
    (rounded up to ``unit``, never balanced down: a floor is a
    floor)."""
    floor = min(floor, extent)
    b = next(c for c in range(floor, extent + 1) if extent % c == 0)
    return b if b <= 2 * floor else min(_ceil_to(floor, unit), extent)


def grown_block(extent: int, b: int, unit: int = 1) -> Optional[int]:
    """The next block of a round of growth: twice ``b`` where that
    divides ``extent`` (if ``2b`` does not, no ``2^j b`` does), else
    the balanced block of half as many tiles; ``None`` where nothing
    larger covers the extent in fewer tiles."""
    nb = 2 * b
    if nb <= extent and extent % nb == 0:
        return nb
    nb = _balanced(extent, nb, unit)
    return nb if nb > b and -(-extent // nb) < -(-extent // b) else None


def plan_blocks(program, fuse_steps: int = 1,
                vmem_budget: int = _INTERPRET_PLAN_BUDGET,
                vinstr_cap: int = 100_000,
                min_block: Optional[Dict[str, int]] = None,
                margin_override: Optional[Dict[str, int]] = None,
                sizer: Optional[Callable[[Dict[str, int]], BlockPrice]]
                = None,
                trail: Optional[List[Dict]] = None) -> Dict[str, int]:
    """Choose leading-dim block sizes for the Pallas path.

    ``sizer`` prices a candidate block: the build hands in its own
    accounting (:class:`BlockPrice`), so the planner keeps no byte or
    instruction estimate of its own; a direct call without one gets the
    uniform tiling's from the build
    (:func:`~yask_tpu.ops.pallas_stencil.block_sizer`).  Blocks grow
    while ``vmem_budget`` holds ``_PIPELINE_COPIES`` copies of what the
    build double-buffers and one of what it does not (``over_budget``
    below); what Mosaic holds on top is the capability table's
    live-value model, which the build tests the chosen block against.

    ``vinstr_cap`` bounds the estimated Mosaic vector-instruction count
    of one fused kernel: the sum, over the equations of every stage of
    every fused sub-step, of the operations a point the evaluation memo
    emits for the equation (a node that several trees of a part hold
    counted once, in the equation that reaches it first: what the
    kernel executes) times the vector registers of the region the
    kernel evaluates it on (the stage's region; a scratch var's grown
    by its write halo).  Block
    growth stops at the cap so op-heavy kernels (ssg, awp, tti) cannot
    reach tile sizes whose Mosaic schedule blows up compile time
    (>15 min observed mid-r3 on ssg-K2).  0 disables the cap.  What the
    estimate reads for ``tti`` radius 4 at 512^3 (184 + 11 operations a
    point on the block's own b_x x b_y x 512, 56 + 56 on the scratch
    vars' (b_x+8) x (b_y+8) x 520, in registers of 8 x 128): 8x8
    24 160, 16x16 65 280, 16x32 and 32x16 117 120.  Mosaic compiled
    16x16 in 45 s and the last two in 95 and 108 s (builder's, PR 33);
    those two are over the class's VMEM room whatever the cap says.
    Until PR 55 the estimate multiplied every equation's whole tree
    (30 752 / 90 528 / 167 136 for the same blocks; ``lbm_d3q19``,
    whose nineteen equations share their density, velocity and
    equilibrium terms, read 6 599 operations a point for the 280 its
    kernel executes, and 105 584 at the smallest block there is), and
    until PR 35 it charged every operation the registers of the whole
    input tile (179 640 / 319 360 / 479 040), 2-5 times what it
    touches; the default cap of 300 000 dated from that estimate.
    Since PR 42 the default is 100 000: about a minute
    of Mosaic on the chip's host, read off one-equation kernels, whose
    trees are their DAG (``iso3dfd_sponge`` r=8 K=2 at 801 x
    801 x 187, the first call's seconds, builder's, PR 42: 31x48
    58 032 -> 26.1 s, 32x48 59 520 -> 26.6, 32x64 79 360 -> 41.0,
    62x48 104 160 -> 64.2, 64x48 107 136 -> 66.6; ``tti``, 91 248 by
    its trees then, 65 280 now -> 43.7), and there the two blocks over
    it, which give up the output staging, also ran 22 % slower than the
    plan the cap leaves, 62x24 (52 080, 24.0 s).

    ``margin_override`` replaces the default uniform ``2·r·K`` TOTAL
    tile margin per dim in the overhead model that orders the growth —
    the build passes each skewed dim's ``(K+1)·r + E_sk``.  Each round
    takes the doubling that model prices lowest, a tie going to the
    outer dim.  In the class whose halo is fetched and never evaluated
    (K=1, one stage, no in-tile scratch) the model rounds the sublane
    dim's extent up to the sublane tile, as the build rounds its DMA
    windows, so that dim grows first where a window rounds (``b_y + 2``
    rows modelled, ``b_y + 8`` fetched); every other class is ordered
    by the plain halo (``window_unit`` below says why).

    ``trail``, a list, receives every candidate priced after the first
    guess, in order: ``{"block", "verdict"}`` with the verdict
    ``taken``, or the reading that ended the growth: ``cap``,
    ``budget``, or ``extent`` (nothing larger covers the span in fewer
    tiles; the block is the plan itself).

    ``min_block`` floors (the skew carry needs blocks ≥ (ring+1)·r in
    every skewed dim) are applied AFTER the first guess and never yield
    a block below the floor.

    A block need not divide its extent (PR 42).  The first guess, a
    floor and each round of growth keep the divisor they always took
    where one is near (:func:`first_block`, :func:`floor_block`,
    :func:`grown_block`: an extent that is a multiple of 64 plans as it
    did) and take the plain value where none is; a candidate that does
    not divide is priced by the points its last tile walks past the
    edge (``ceil(extent / b) · b / extent``) on top of its halo, and
    taken only if that still lowers the modelled overhead.  801 = 3² ×
    89 at radius 8, K=2 plans 3 × 64 by divisors alone.
    """
    ana = program.ana
    dims = ana.domain_dims
    lead = dims[:-1]
    sizes = {d: program.sizes[d] for d in dims}
    rad = ana.fused_step_radius()
    hK = {d: rad.get(d, 0) * fuse_steps for d in lead}
    # TOTAL extra tile width per dim in the overhead model below
    # (both-side margins); the skewed stream dim fetches less than 2*hK
    marg = {d: 2 * hK[d] for d in lead}
    for d, m in (margin_override or {}).items():
        if d in marg:
            marg[d] = m
    sub = get_capability().sublane_count(program.dtype)
    if sizer is None:
        from yask_tpu.ops.pallas_stencil import block_sizer
        sizer = block_sizer(program, fuse_steps)

    fold = program.soln.get_settings().fold

    # initial guess: fold hints, else sublane multiple for next-to-minor,
    # small for outers
    block: Dict[str, int] = {}
    for i, d in enumerate(lead):
        if fold.has_dim(d) and fold[d] > 0:
            block[d] = min(fold[d], sizes[d])
        elif i == len(lead) - 1:
            block[d] = min(sub, sizes[d])
        else:
            block[d] = min(8, sizes[d])

    # a divisor of the extent where one is near, else the plain value:
    # the kernel covers a span by ceil and masks what lies past the
    # edge, and prepare pads the arrays for the overshoot
    # (``StencilContext._pallas_pad_needs``)
    unit = {d: sub if d == lead[-1] else 1 for d in lead}
    for d in lead:
        block[d] = first_block(sizes[d], block[d], unit[d])
    # what a tile's modelled extent rounds up to in the overhead model
    # below.  A kernel of one sub-step of one stage with no in-tile
    # scratch evaluates its block and nothing else (the build's
    # ``margin_overhead`` is 0.0 by construction): its halo is DMA
    # windows alone, and the build rounds a window of the sublane dim
    # out to the sublane tile (``b_y + 1`` rows are fetched as ``b_y +
    # 8``), so there a doubling of the sublane dim buys bytes where the
    # plain halo says it buys as little as the lead dim's.  Every other
    # class evaluates its halo too and keeps the plain model: where its
    # margins are multiples of the tile (every cell of radius 8, the
    # K=4 kernels of radius 1) the rounding changes nothing, and where
    # they are not (a K=2 kernel of radius 1) the plans that run today
    # were timed under the plain one (``PERF.md`` section 7)
    fetch_only = (fuse_steps == 1 and len(ana.stages) == 1
                  and not ana.tile_scratch)
    window_unit = {d: unit[d] if fetch_only else 1 for d in lead}

    def over_cap(price: BlockPrice) -> bool:
        return bool(vinstr_cap) and price.vinstr > vinstr_cap

    def over_budget(price: BlockPrice,
                    copies: int = _PIPELINE_COPIES) -> bool:
        """Blocks grow while the budget holds two copies of what the
        build double-buffers (the input tiles; one result tile a fused
        sub-step, the output staging's unit) and one of what it never
        does (in-tile scratch, skew carry, pushed rings).  The result
        tile of each sub-step beyond the first is the depth term: a
        deeper fusion stops at smaller blocks (round 3: a K-chain whose
        tiles fit the budget died in compile with 140 MiB of spill
        slots).  It is the one term here beside the capability table's
        live tiles (ROADMAP D6), kept because the plans that run today
        rest on it (cube K=4 at 768^3 plans 32x16 with it), and priced
        by the build's own result tile.

        A class priced as ``declared`` has no such term: the strip
        kernel holds no result tile a sub-step, so the price is what
        the build will declare with the input pipeline on, ``copies``
        of the input tiles and the work tiles once, refused by the
        test the build itself makes (``tile_bytes > vmem_budget``)."""
        if price.declared:
            return copies * price.in_bytes + price.work_bytes > vmem_budget
        doubled = price.in_bytes + fuse_steps * price.result_bytes
        once = price.work_bytes - price.result_bytes
        return _PIPELINE_COPIES * doubled + once >= vmem_budget

    # per-dim floors (the skew carry needs stream blocks ≥ (ring+1)·r —
    # without this the default plan silently forfeits the skewed
    # tiling).  The floor must bypass neither the vinstr compile-time
    # guard nor the budget: if the floored plan busts either, leave the
    # dim alone and let the build fall back to the uniform tiling.  A
    # ``declared`` class holds the floor to what the build holds the
    # skew to, one copy of the input tiles (it turns the input pipeline
    # off where two do not fit): a floor the build would accept is not
    # refused here.
    for d, mn in (min_block or {}).items():
        if d in block and block[d] < mn:
            b = floor_block(sizes[d], mn, unit[d])
            cand = dict(block)
            cand[d] = b
            price = sizer(cand)
            if not (over_cap(price) or over_budget(price, copies=1)):
                block[d] = b

    def overhead(blk):
        """Read-reuse model: fraction of each tile's loads + compute that
        is halo overlap recomputed by neighboring tiles — the quantity
        the reference's fold planner minimizes as 'reads per point'
        (``Vec.*``). Growing the dim with the worst surface/volume ratio
        first buys the most reuse per VMEM byte."""
        interior = 1
        padded = 1
        walked = 1
        for d in lead:
            interior *= blk[d]
            padded *= _ceil_to(blk[d] + marg[d], window_unit[d])
            walked *= -(-sizes[d] // blk[d]) * blk[d]
        ov = (padded - interior) / max(interior, 1)
        # a block that does not divide its extent also pays for the
        # points its last tile walks past the edge (evaluated, then
        # masked): the tiling record's ``edge_overhead``
        span = math.prod(sizes[d] for d in lead)
        return ov if walked == span else (1 + ov) * walked / span - 1

    # each round takes the doubling that cuts the modelled overhead
    # most (a tie goes to the outer dim) if its price fits, and growth
    # ends when it does not: doubling can only reduce (or, for
    # zero-halo dims, preserve) the overhead, and either way shrinks
    # the grid.  A refused best does not fall through to the other
    # dim's doubling: outside the fetch-only class the model still ties
    # two doublings that the build's count no longer prices the same
    # (the sublane dim's slab rounds up to 8), and the plans that run
    # today came from the tie.
    if trail is None:
        trail = []
    while True:
        best = None
        for d in lead:
            nb = grown_block(sizes[d], block[d], unit[d])
            if nb is None:
                continue
            cand = dict(block)
            cand[d] = nb
            ov = overhead(cand)
            # a doubling that divides is taken as it always was; a
            # block that does not divide must pay for its overshoot
            if sizes[d] % nb != 0 and ov >= overhead(block):
                continue
            if best is None or ov < best[0]:
                best = (ov, cand)
        if best is None:
            trail.append({"block": dict(block), "verdict": "extent"})
            return block
        price = sizer(best[1])
        verdict = ("budget" if over_budget(price)
                   else "cap" if over_cap(price) else "taken")
        trail.append({"block": dict(best[1]), "verdict": verdict})
        if verdict != "taken":
            return block
        block = best[1]
