"""Pallas stencil kernels: halo tiles in VMEM + K-step temporal fusion.

This is the TPU replacement for the reference's generated inner loops
(vector folding + nano/pico loops, ``YaskKernel.cpp:574-676``) *and* its
temporal wave-front tiling (``context.hpp:331-347``): one kernel invocation

1. DMAs an (bx+2·r·K, by+2·r·K, Nz_padded) halo tile of each input var
   from HBM into a VMEM buffer (the fold/tile planner's job: the
   minor-most dim stays whole so it rides the 128-lane axis);
2. applies **K fused time steps** entirely in VMEM — the compute region
   shrinks by the stencil radius each sub-step (or, skewed, slides along
   the stream dim), and a global-domain mask keeps physical-boundary
   ghosts at zero between sub-steps (matching the runtime's ghost
   semantics);
3. writes the final (and, for 2-slot rings, the previous) time level's
   interior block back.

HBM traffic per K steps ≈ one read + one write of each var, versus K of
each for the unfused path — the same arithmetic-intensity win wave-front
tiling buys the reference.

**How a grid step evaluates a block (the strip evaluator, PR 44).**  The
tiles STAY in their VMEM buffers (refs).  Each stage of each fused
sub-step walks its region in *strips* — a few rows of the untiled lead
dims × the sublane extent × the whole minor extent, ONE value of 50–100
vector registers (``_StripEval``; the chip wants strips large: each pays
a fixed cost beside its schedule): a loop over the lead rows (a dynamic
row index into the refs) with the sublane groups unrolled inside, so
Mosaic compiles one strip's body, not the region's.  A strip reads its
operands from the refs (windows that differ only in their lead-row
offset are rows of one cover window, loaded and shifted into place
once; a sublane or lane offset is a shifted window; rows that start off
the 8-row register tile are rotated onto it where that pays),
evaluates the equation with the same AST walk
and the same operations a point as the whole-tile evaluator, applies
the domain mask and the equation's conditions on the strip, and stores
the strip where it belongs: into the evicted ring slot's buffer where
one equation writes the var and reads that slot only at the point it
writes (iso3dfd's ``p(t-1)``: in place), else into an explicit result
tile seeded with the evicted slot's; a scratch var's into its own
tile.  The ring is a rotation of buffer NAMES (``_plan_strips``, static):
the produced slots are DMA'd out of the buffers the ring left them in,
or, where the write-back is pipelined, their output windows are copied
to the parity's staging tile.  The skew carry's patches and saves are
ref-to-ref copies of their strips.  What the planner counts is
unchanged: the explicit tiles are inside the work bytes of the plan.

The **whole-tile evaluator** (``_TileEval``: every tile loaded as one
value, every intermediate a region-sized value, results put back by
``lax.pad`` + iota masks + select) remains for the push arm, which has
never met Mosaic, and for a solution with no lead dim; the tests hold
the strip evaluator to it bit for bit
(``tests/strip_cases.py``).  The tiling record says which a chunk got
(``eval``, ``strip``, ``strips``, ``strip_vregs``).

Applicability (checked by :func:`pallas_applicable`): every var's last
domain dim must be the solution minor (Mosaic lane-DMA alignment) and
its domain dims must follow solution order.  Multi-stage chains, sub-
domain/step conditions, scratch-var chains (evaluated in-tile over
write-halo-expanded regions), misc-dim vars, partial-dim vars (read,
written, or scratch — their RHS is constant along the missing dims per
the analysis race rule), 1-D solutions (one full-lane tile), and
arbitrary ring depth are all handled in-kernel; the rest falls back to
the XLA-fused path.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Tuple

from yask_tpu.utils.exceptions import YaskException
from yask_tpu.ops.tile_planner import (_INTERPRET_PLAN_BUDGET,
                                       BlockPrice, TilePlan)
from yask_tpu.compiler.expr import (
    AddExpr,
    AndExpr,
    CompExpr,
    ConstExpr,
    DivExpr,
    Expr,
    FirstIndexExpr,
    FuncExpr,
    IndexExpr,
    LastIndexExpr,
    ModExpr,
    MultExpr,
    NegExpr,
    NotExpr,
    OrExpr,
    SubExpr,
    VarPoint,
)


def pallas_applicable(csol) -> Tuple[bool, str]:
    """Can this solution run on the Pallas fused path? Supported: multi-
    stage chains (ssg/fsg-class), sub-domain/step conditions (awp-class —
    lowered to in-tile masks over global coordinates), index-value
    expressions, partial-dim vars — read-only coefficients (sponge
    factors), written, and scratch alike, their RHS being constant
    along the missing dims per the analysis race rule — scratch-var
    chains evaluated in-tile over expanded regions (tti/swe2d-class),
    misc-dim vars including written ones (filter kernels — constant LHS
    misc values pin the write), 1-D solutions (one full-lane tile,
    empty grid), and any ring allocation (deep time reads,
    2nd-order-in-time schemes). Excluded: vars whose last domain dim is
    not the solution minor (Mosaic lane-DMA alignment) and written vars
    with no domain dims at all."""
    ana = csol.ana
    if not ana.domain_dims:
        return False, "needs >= 1 domain dim"
    # 1-D solutions tile as a single full-lane block (empty grid): the
    # whole padded line is one VMEM tile, K-fusion included
    minor = ana.domain_dims[-1]
    for v in csol.soln.get_vars():
        dd = v.domain_dim_names()
        if v.is_written:
            # Partial-dim written vars are supported when they keep the
            # minor (lane) dim: the RHS is constant along the missing
            # lead dims (same rule the XLA path's _to_var_layout
            # applies), so every tile computes the identical slab and
            # the sequential grid's repeated write-back is benign.  A
            # written var missing the minor dim would need lane-axis
            # DMA windows at non-128 offsets (Mosaic rule below).
            if not dd:
                return False, (f"written var '{v.get_name()}' has no "
                               "domain dims (per-step scalar reduction "
                               "stays on the XLA path)")
            if dd[-1] != minor:
                return False, (f"written var '{v.get_name()}' lacks the "
                               f"minor dim '{minor}' as its last domain "
                               "dim (Mosaic lane-DMA alignment)")
            if dd != [d for d in ana.domain_dims if d in dd]:
                return False, (f"var '{v.get_name()}' declares domain "
                               "dims out of solution order")
        else:
            # Mosaic DMA windows constrain the lane (last physical) axis
            # to 128-aligned full-extent fetches; a read-only var whose
            # lane axis is a *lead* dim would need pid-dependent lane
            # offsets, which TC vector loads cannot do (probed on v5e).
            if dd and dd[-1] != minor:
                return False, (f"read-only var '{v.get_name()}' lacks the "
                               f"minor dim '{minor}' as its last domain "
                               "dim (Mosaic lane-DMA alignment)")
            if dd and dd != [d for d in ana.domain_dims if d in dd]:
                return False, (f"var '{v.get_name()}' declares domain dims "
                               "out of solution order")

    return True, "ok"


# ---------------------------------------------------------------------------


class _TileEval:
    """Evaluate the stencil AST on VMEM tile values.

    ``tiles[name]`` is the ring of tile arrays (oldest→newest); a read at
    offset ``o`` over compute-region ``lo..hi`` (tile coords, leading
    dims; interior-relative for the minor dim) slices ``[lo+o : hi+o]``
    with the var's own origins. Partial-dim read-only vars broadcast into
    the region; index expressions produce *global* coordinate arrays so
    conditions behave identically to the XLA path.
    """

    def __init__(self, jnp, program, minor: str,
                 minor_origin: Dict[str, int],
                 resid: Optional[Dict[Tuple[str, str], int]] = None):
        self.jnp = jnp
        self.program = program
        self.resid = resid or {}   # (var, lead dim) -> static tile shift
        self.dims = program.ana.domain_dims
        self.minor = minor
        self.step_dir = program.ana.step_dir
        self.minor_origin = minor_origin
        from yask_tpu.compiler.lowering import JnpOps
        self.ops = JnpOps()
        # set per-(stage, sub-step) by the kernel before evaluation:
        self.region = None          # [(lo,hi)] per solution dim
        self.gidx_base = None       # per lead dim: traced global offset of
        #                             tile position 0 (pid*block - hK)
        self.t = None               # step-index value (traced or None)
        self.scratch = {}           # scratch var -> full-tile value
        self.misc_env = {}          # current equation's LHS misc binding

    def global_index(self, d: str):
        """Global coordinate array for dim d over the current region,
        broadcast-shaped. ``gidx_base`` maps tile position 0 to the
        global-problem coordinate (it includes the shard offset in
        distributed mode)."""
        di = self.dims.index(d)
        lo, hi = self.region[di]
        shape = [1] * len(self.dims)
        shape[di] = hi - lo
        # broadcasted_iota, not 1-D arange+reshape: Mosaic TC crashes on
        # non-lane-axis 1-D iota (probed on TPU v5e)
        from jax import lax
        ar = lax.broadcasted_iota(self.jnp.int32, tuple(shape), di) + lo
        base = self.gidx_base.get(d)
        if base is not None:
            ar = ar + base
        return ar

    def source(self, p: VarPoint, tiles, computed):
        """What a read resolves to: the scratch var's tile, an earlier
        stage's result of this sub-step, or a slot of the var's ring."""
        name = p.var_name()
        g = self.program.geoms[name]
        so = p.step_offset()
        if g.is_scratch:
            # Scratch values live as full tiles computed earlier in
            # this sub-step over an expanded region, so offset slicing
            # works exactly like ring tiles.
            return self.scratch[name]
        if name in computed and so is not None and so == self.step_dir:
            # Same-step read of an earlier stage's output: computed
            # results are kept as FULL tiles (the evicted base with the
            # region written over it), so offset slicing works exactly
            # like rings.
            return computed[name]
        ring = tiles[name]
        if so is None or not g.is_written:
            return ring[-1]
        idx = len(ring) - 1 + so * self.step_dir
        if not (0 <= idx < len(ring)):
            # mirror the XLA path's bounds check — a negative
            # Python index would silently wrap to the newest slot
            raise YaskException(
                f"step offset {so} of '{name}' outside its "
                f"allocation {len(ring)}")
        return ring[idx]

    def read(self, p: VarPoint, tiles, computed):
        name = p.var_name()
        g = self.program.geoms[name]
        region = self.region
        arr = self.source(p, tiles, computed)
        offs = p.domain_offsets()
        misc = p.misc_vals()
        idxs = []
        for dn, kind in g.axes:   # var's own axis order
            if kind == "misc":
                idxs.append(misc[dn] - g.misc_lo[dn])
                continue
            di = self.dims.index(dn)
            lo, hi = region[di]
            o = offs.get(dn, 0)
            if dn == self.minor:
                base = self.minor_origin[name]
                idxs.append(slice(base + lo + o, base + hi + o))
            else:
                rs = self.resid.get((name, dn), 0)
                idxs.append(slice(rs + lo + o, rs + hi + o))
        if not g.axes:
            out = arr[0]   # 0-dim var rides SMEM as shape (1,)
        else:
            out = arr[tuple(idxs)]

        var_dd = g.domain_dims
        if var_dd != self.dims:
            # partial-dim (or reordered) var: transpose into solution
            # order, insert singleton axes, broadcast over the region
            present = [d for d in self.dims if d in var_dd]
            perm = [var_dd.index(d) for d in present]
            if perm != list(range(len(perm))):
                out = out.transpose(perm)
            shape = []
            for d in self.dims:
                di = self.dims.index(d)
                lo, hi = region[di]
                shape.append(hi - lo if d in var_dd else 1)
            out = out.reshape(tuple(shape))
            tgt = tuple(hi - lo for lo, hi in region)
            out = self.jnp.broadcast_to(out, tgt)
        return out

    def eval(self, e: Expr, tiles, computed, memo):
        k = e.skey()   # structural: CSE across equations within a sub-step
        if k in memo:
            return memo[k]
        jnp = self.jnp
        ev = lambda a: self.eval(a, tiles, computed, memo)
        if isinstance(e, ConstExpr):
            r = e.value
        elif isinstance(e, VarPoint):
            r = self.read(e, tiles, computed)
        elif isinstance(e, IndexExpr):
            if e.type.value == "step":
                r = self.t
            elif e.type.value == "domain":
                r = self.global_index(e.name)
            else:
                # per-equation LHS-pinned constant; never memoized (the
                # node recurs in sibling eqs with different bindings)
                mv = self.misc_env or {}
                if e.name not in mv:
                    raise YaskException(
                        f"misc index '{e.name}' used as a value outside "
                        "an equation that pins it on the LHS")
                return mv[e.name]
        elif isinstance(e, FirstIndexExpr):
            r = 0
        elif isinstance(e, LastIndexExpr):
            r = self.program.global_last[e.dim.name]
        elif isinstance(e, NegExpr):
            r = -ev(e.arg)
        elif isinstance(e, AddExpr):
            r = ev(e.args[0])
            for a in e.args[1:]:
                r = r + ev(a)
        elif isinstance(e, MultExpr):
            r = ev(e.args[0])
            for a in e.args[1:]:
                r = r * ev(a)
        elif isinstance(e, SubExpr):
            r = ev(e.lhs) - ev(e.rhs)
        elif isinstance(e, DivExpr):
            r = ev(e.lhs) / ev(e.rhs)
        elif isinstance(e, ModExpr):
            r = ev(e.lhs) % ev(e.rhs)
        elif isinstance(e, FuncExpr):
            from yask_tpu.compiler.expr import paired_func_eval
            r = paired_func_eval(
                self.ops.func, e, [ev(a) for a in e.args], memo,
                getattr(self.program.ana, "sincos_args", ()))
        elif isinstance(e, CompExpr):
            a, b = ev(e.lhs), ev(e.rhs)
            r = {"==": lambda: a == b, "!=": lambda: a != b,
                 "<": lambda: a < b, "<=": lambda: a <= b,
                 ">": lambda: a > b, ">=": lambda: a >= b}[e.op]()
        elif isinstance(e, AndExpr):
            r = jnp.logical_and(ev(e.lhs), ev(e.rhs))
        elif isinstance(e, OrExpr):
            r = jnp.logical_or(ev(e.lhs), ev(e.rhs))
        elif isinstance(e, NotExpr):
            r = jnp.logical_not(ev(e.arg))
        else:  # pragma: no cover - excluded by pallas_applicable
            raise YaskException(f"pallas path cannot evaluate {type(e)}")
        memo[k] = r
        return r


def _eq_points(eq) -> list:
    """Every var point an equation reads: its right-hand side and both
    conditions."""
    from yask_tpu.compiler.expr import PointVisitor
    pv = PointVisitor()
    eq.rhs.accept(pv)
    if eq.cond is not None:
        eq.cond.accept(pv)
    if eq.step_cond is not None:
        eq.step_cond.accept(pv)
    return pv.points


def _eq_cost(eq, sincos_args) -> int:
    """Register operations a point of an equation, as the strip
    evaluator's layout rule weighs them: its counted operations, an
    elementary function (sin, cos: a range reduction and a polynomial
    on the vector unit) at ~100."""
    from yask_tpu.compiler.expr import CounterVisitor, ExprVisitor

    class FuncCounter(ExprVisitor):
        count = 0

        def visit_func(self, node):
            self.count += 1
            return self._visit_children(node)

    ops, funcs = CounterVisitor(sincos_args=sincos_args), FuncCounter()
    eq.accept(ops)
    eq.accept(funcs)
    return ops.num_ops + 100 * funcs.count


class _Buf:
    """A VMEM (or SMEM) buffer the strip evaluator reads and stores:
    the ref, and a static key that says which buffer it is (a ring
    slot's input tile, a var's explicit result tile, a scratch var's
    tile) to the load cache and to the plan's hazard rules."""

    __slots__ = ("key", "ref")

    def __init__(self, key, ref):
        self.key = key
        self.ref = ref


class _StripEval(_TileEval):
    """Evaluate the stencil AST on one STRIP of a stage's region, read
    from the VMEM refs: the same walk and the same operations a point
    as :class:`_TileEval`, on values of a few vector registers.

    ``tiles[name]`` is the ring of :class:`_Buf` (oldest→newest),
    ``computed`` and ``scratch`` map a var to the buffer that holds its
    result.  ``strip`` gives, per solution dim, ``(base, off, size)``:
    the strip starts at ``base + off`` in tile coordinates (``base`` the
    traced index of the walk's loop over an untiled lead dim, or None)
    and spans ``size`` points.  A lead-dim offset of a read is another
    row index of the ref; every load is kept in ``loads`` under its
    static description, so rows of one loop body that read the same
    window share it."""

    def __init__(self, *args, pl=None, pltpu=None, sub_t=8):
        super().__init__(*args)
        self.pl = pl
        self.pltpu = pltpu
        self.sub_t = sub_t
        self.strip = None
        self.loads = {}
        self.row_cover = {}
        self.realign = False

    def shape(self):
        return tuple(sz for _b, _o, sz in self.strip)

    def global_index(self, d: str):
        di = self.dims.index(d)
        sbase, off, size = self.strip[di]
        shape = [1] * len(self.dims)
        shape[di] = size
        from jax import lax
        ar = lax.broadcasted_iota(self.jnp.int32, tuple(shape), di) + off
        if sbase is not None:
            ar = ar + sbase
        base = self.gidx_base.get(d)
        if base is not None:
            ar = ar + base
        return ar

    def window(self, name, offs=None, misc=None, grow=0):
        """``(index tuple, static description)`` of the strip's window
        of var ``name`` over its own axes, at domain offsets ``offs``:
        misc axes pinned (they collapse), lead dims sliced at the
        strip's rows, the minor dim at the var's pad origin."""
        g = self.program.geoms[name]
        offs = offs or {}
        idxs, desc = [], []
        for dn, kind in g.axes:
            if kind == "misc":
                idxs.append(misc[dn] - g.misc_lo[dn])
                desc.append(idxs[-1])
                continue
            sbase, off, size = self.strip[self.dims.index(dn)]
            if grow and dn == self.dims[-3]:
                size += grow
            if dn == self.minor:
                off = off + self.minor_origin[name]
            else:
                off = off + self.resid.get((name, dn), 0)
            off = off + offs.get(dn, 0)
            if sbase is None:
                idxs.append(slice(off, off + size))
            else:
                idxs.append(self.pl.ds(sbase + off, size))
            desc.append((off, size))
        return tuple(idxs), tuple(desc)

    def _tiles(self, buf, name, idxs, desc):
        """Where ``realign`` is on and the window's sublane rows start
        off the register tile: ``(axis of the value, its aligned cover
        as an index tuple, rows of the cover, rows the window starts
        into it)``; else None (also where the cover would leave the
        buffer)."""
        g = self.program.geoms[name]
        if not self.realign or len(g.axes) < 2 \
                or g.axes[-2] != (self.dims[-2], "domain"):
            return None
        off, size = desc[-2]
        shift = off % self.sub_t
        cover = -(-(shift + size) // self.sub_t) * self.sub_t
        if not shift or off - shift + cover > buf.ref.shape[-2]:
            return None
        vax = sum(isinstance(d, tuple) for d in desc) - 2
        return (vax, idxs[:-2] + (slice(off - shift, off - shift + cover),
                                  idxs[-1]), cover, shift)

    @staticmethod
    def cover_key(key, offs, misc):
        """What names a window apart from the lead row it is read at:
        the buffer, the misc indices and every other offset."""
        return (key, tuple(sorted((misc or {}).items())),
                tuple(sorted((d, o) for d, o in offs.items() if o)))

    def load(self, buf, name, offs=None, misc=None):
        """The strip's window of a buffer at domain offsets ``offs``.
        Windows that differ only in their lead-row offset (a star's
        neighbours along the lead dim, a diagonal stencil's shifted
        windows) are rows of ONE cover window, loaded -- and shifted
        into place -- once, and sliced along the untiled lead axis."""
        g = self.program.geoms[name]
        rowdim = self.dims[-3] if len(self.dims) > 2 else None
        offs = dict(offs or {})
        if rowdim in g.domain_dims:
            o = offs.pop(rowdim, 0)
            lo, hi = self.row_cover.get(
                self.cover_key(buf.key, offs, misc), (o, o))
            lo, hi = min(lo, o), max(hi, o)
            if hi > lo:
                from jax import lax
                rows = self.strip[-3][2]
                ax = sum(kind == "domain" for _dn, kind
                         in g.axes[:g.axes.index((rowdim, "domain"))])
                return lax.slice_in_dim(
                    self.window_value(buf, name, {**offs, rowdim: lo},
                                      misc, grow=hi - lo),
                    o - lo, o - lo + rows, axis=ax)
            offs[rowdim] = o
        return self.window_value(buf, name, offs, misc)

    def window_value(self, buf, name, offs=None, misc=None, grow=0):
        """The strip's window of a buffer, ``grow`` lead rows longer.
        A window whose sublane rows
        start off the (8-row) register tile would make every value
        computed from it a register taller than its rows need; under
        ``realign`` its aligned cover is loaded and rotated so that the
        strip's values sit on the tile (a sublane rotate a register: the
        rotates ride their own slot)."""
        idxs, desc = self.window(name, offs, misc, grow)
        key = (buf.key, desc, self.realign)
        if key not in self.loads:
            tiles = self._tiles(buf, name, idxs, desc)
            if tiles is None:
                val = buf.ref[idxs]
            else:
                from jax import lax
                vax, cover_idxs, cover, shift = tiles
                val = lax.slice_in_dim(
                    self.pltpu.roll(buf.ref[cover_idxs], cover - shift,
                                    vax), 0, desc[-2][1], axis=vax)
            self.loads[key] = val
        return self.loads[key]

    def store(self, buf, name, val, misc=None, whole_lanes=False):
        """Put a strip's result where it belongs (rotated back off the
        tile where its window starts there); what the cache held of
        that buffer is stale from here on.  ``whole_lanes`` stores the
        rows' whole minor extent, zeros either side of the result: the
        minor pads of a buffer no DMA filled, which every read at a
        minor offset and every output copy takes to be zero."""
        idxs, desc = self.window(name, None, misc)
        kept = val
        if whole_lanes:
            from jax import lax
            off, size = desc[-1]
            total = buf.ref.shape[-1]
            val = lax.pad(val, self.jnp.zeros((), val.dtype),
                          [(0, 0, 0)] * (val.ndim - 1)
                          + [(off, total - off - size, 0)])
            idxs = idxs[:-1] + (slice(0, total),)
        tiles = self._tiles(buf, name, idxs, desc)
        if tiles is None:
            buf.ref[idxs] = val
        else:
            from jax import lax
            vax, _cover_idxs, cover, shift = tiles
            rows = desc[-2][1]
            pad = list(val.shape)
            pad[vax] = cover - rows
            back = self.pltpu.roll(self.jnp.concatenate(
                [val, self.jnp.zeros(tuple(pad), val.dtype)], axis=vax),
                shift, vax)
            buf.ref[idxs] = lax.slice_in_dim(back, shift, shift + rows,
                                             axis=vax)
        for k in [k for k in self.loads if k[0] == buf.key]:
            del self.loads[k]
        self.loads[buf.key, desc, self.realign] = kept

    def read(self, p: VarPoint, tiles, computed):
        name = p.var_name()
        g = self.program.geoms[name]
        buf = self.source(p, tiles, computed)
        if not g.domain_dims:
            # SMEM riders: a static scalar read (0-dim vars as (1,))
            if not g.axes:
                return buf.ref[0]
            return buf.ref[self.window(name, None, p.misc_vals())[0]]
        out = self.load(buf, name, p.domain_offsets(), p.misc_vals())
        if g.domain_dims != self.dims:
            # partial-dim var: singleton axes for the dims it lacks,
            # broadcast over the strip
            tgt = self.shape()
            out = out.reshape(tuple(
                sz if d in g.domain_dims else 1
                for d, sz in zip(self.dims, tgt)))
            out = self.jnp.broadcast_to(out, tgt)
        return out


# ---------------------------------------------------------------------------


class _DomainEval(_TileEval):
    """Evaluate a condition that names nothing but domain indices over
    the whole problem's domain, in numpy: each index an ``arange``
    along its own dim, so a value spans only the dims it names."""

    def __init__(self, program):
        import numpy as np
        super().__init__(np, program, program.ana.domain_dims[-1], {})

    def global_index(self, d: str):
        shape = [1] * len(self.dims)
        shape[self.dims.index(d)] = self.program.global_last[d] + 1
        return self.jnp.arange(shape[self.dims.index(d)]).reshape(shape)


def conds_cover_domain(program, conds) -> bool:
    """Do these sub-domain conditions (None: everywhere), together,
    select every point of the problem's domain?  Decided on the
    domain's own indices; False where a condition reads a var, the
    step index, a misc index or a function, or names so many dims that
    its value would be a field of its own: nothing cheap says then
    what it selects.  (``analysis.kept_vars`` asks: an input slot
    whose old values show nowhere need not be fetched.)"""
    from yask_tpu.compiler.expr import ExprVisitor, IndexType, \
        used_domain_dims
    if any(c is None for c in conds):
        return True

    class IndexOnly(ExprVisitor):
        ok = True

        def visit_index(self, node):
            self.ok = self.ok and node.type == IndexType.DOMAIN

        def visit_var_point(self, node):
            self.ok = False

        def visit_func(self, node):
            self.ok = False

    seen = IndexOnly()
    for c in conds:
        c.accept(seen)
    if not seen.ok or math.prod(
            program.global_last[d] + 1
            for d in used_domain_dims(*conds)) > 2 ** 24:
        return False
    ev = _DomainEval(program)
    sel = False
    for c in conds:
        sel = ev.jnp.logical_or(sel, ev.eval(c, {}, {}, {}))
    return bool(ev.jnp.all(sel))


def skew_eligible_dims(program, fuse_steps: int) -> List[str]:
    """The dims the skewed wavefront CAN run on, feasibility only: the
    stream dim (``lead[-1]``, the innermost grid dim — consecutive
    sequential grid steps, so a tile's strips carry to the next tile)
    or none.  Every other lead dim keeps the uniform shrink.  The
    stream dim qualifies when its fused radius is > 0, K ≥ 2 and every
    written var spans all domain dims (a partial-dim write slab's
    slice index would become pid-dependent under skewed regions)."""
    ana = program.ana
    lead = ana.domain_dims[:-1]
    if fuse_steps < 2 or not lead:
        return []
    for g in program.geoms.values():
        if g.is_written and not g.is_scratch \
                and g.domain_dims != ana.domain_dims:
            return []
    rad = ana.fused_step_radius()
    return [d for d in lead[-1:] if rad.get(d, 0) > 0]


def skew_extra_width(dtype, r: int) -> int:
    """E_sk: the extra computed sublane-dim width a skewed region needs
    when the radius is not a sublane multiple (write-back shifts round
    DOWN to the tile and the window widens by one tile; need
    E ≥ d + sub_t with d = shift−floor(shift) < sub_t ⇒ 2·sub_t).
    THE single definition — the profit gate, the planner hints, the
    build's margins, and the runtime's pad planning must all agree."""
    from yask_tpu.compiler.lowering import tpu_tile_dims
    sub_t, _ = tpu_tile_dims(dtype)
    return 2 * sub_t if r % sub_t != 0 else 0


def skew_extra_widths(program, fuse_steps: int) -> Dict[str, int]:
    """E_sk of every skew-eligible dim (the stream dim, or none): it is
    the sublane (8-aligned-window) axis of the written full-dim vars,
    so it pays the rounding widening."""
    rad = program.ana.fused_step_radius()
    return {d: skew_extra_width(program.dtype, rad.get(d, 0))
            for d in skew_eligible_dims(program, fuse_steps)}


def skew_engaged_dims(program, fuse_steps: int,
                      unsharded=None) -> List[str]:
    """The dims ``build_pallas_chunk`` auto-engages (``skew=None``):
    the stream dim where it is eligible AND passes the profit gate — a
    skewed dim computes (K+1)·r + E_sk extra width per tile vs 2·K·r
    for uniform shrink (misaligned small stream radii lose to their
    own E_sk widening) — else none.  ``unsharded`` restricts to
    mesh-undecomposed dims (carry strips cannot cross shards); ``None``
    = all unsharded (single device).  THE shared definition for the
    build, planner hints, and the HBM traffic model, so the stats
    describe the tiling actually run."""
    rad = program.ana.fused_step_radius()
    e_sk = skew_extra_widths(program, fuse_steps)
    K = fuse_steps
    picked = []
    for d in skew_eligible_dims(program, fuse_steps):
        if unsharded is not None and d not in unsharded:
            continue
        r = rad.get(d, 0)
        if (K + 1) * r + e_sk[d] < 2 * K * r:
            picked.append(d)
    return picked


def skew_plan_hints(program, fuse_steps: int, engaged=None):
    """(min_block, margin_override) for :func:`plan_blocks` when the
    skewed wavefront engages — THE shared definition for the build and
    the auto-tuner's seed plan: the skewed dim's block is floored at
    the carry minimum (ring+1)·r, and its margin modeled as the
    (K+1)·r + E_sk the skew actually fetches (not 2·K·r).  ``engaged``
    overrides the auto decision: ``None`` = auto
    (:func:`skew_engaged_dims`), ``True`` = the stream dim forced,
    ``False`` = none, or an explicit list of dims (the build passes its
    resolved skew set).  Returns (None, None) when skew won't run."""
    ana = program.ana
    lead = ana.domain_dims[:-1]
    if engaged is None:
        engaged = skew_engaged_dims(program, fuse_steps)
    elif engaged is True:
        engaged = [lead[-1]] if lead else []
    elif engaged is False:
        engaged = []
    if not engaged:
        return None, None
    rad = ana.fused_step_radius()
    # the TilePlan is THE margin-math source: hints are read off the
    # dataflow plan rather than recomputed here
    tp = TilePlan(program, fuse_steps, skew_dims=engaged,
                  e_sk={d: skew_extra_width(program.dtype, rad.get(d, 0))
                        for d in engaged})
    return tp.min_block(), tp.margin_override()


def default_vmem_budget(platform: str, device_kind: str = "",
                        fuse_steps: int = 1, stages: int = 1,
                        scratch_vars: int = 0) -> int:
    """Device-derived Pallas VMEM *tile* budget of a kernel fusing
    ``fuse_steps`` steps of a ``stages``-stage program that keeps
    ``scratch_vars`` scratch vars in-tile (overridable via
    ``-vmem_mb``): the class's row of the capability table's one
    live-value model where the chip has measured room
    (``BackendCapability.plan_budget_bytes``, each number with its chip
    runs), else half of Mosaic's scoped limit.  Under CPU interpret
    VMEM is emulated and the budget only shapes planning.  Single
    definition for the runtime context and the checker (a TPU kind
    without an entry raises)."""
    from yask_tpu.backend import capability_for_platform
    return capability_for_platform(
        platform, device_kind).plan_budget_bytes(fuse_steps, stages,
                                                 scratch_vars)


def vmem_limit_bytes(vmem_budget: int) -> int:
    """Scoped Mosaic VMEM limit requested for a given tile budget (the
    cap, 128 MiB, at every default budget).  Single definition — the
    kernel's CompilerParams and the static checker's spill model both
    use it; the numbers live in the capability table."""
    from yask_tpu.backend import get_capability
    return get_capability().vmem_limit_bytes(vmem_budget)


def plan_attrs(tiling: dict) -> dict:
    """The scalars of a built kernel's plan that a ``compile.chunk``
    span carries (span attrs must be scalars, so the block is a
    string, as are the fused step's reach, what each stage has
    consumed of it, the rows the grid walks past each lead dim's edge
    and the rows of right pad they lie in, lead dims joined by ``x``
    and stages by ``,``): what
    says whether the live-value model engaged, and the instruction
    estimate the cap was held against, with the operations a point
    it multiplies (``dag_ops_per_point``: what the evaluation memo
    emits, a shared operation once) beside every equation's whole tree
    (``ops_per_point``), and the reading that ended the default plan's
    growth (``growth_ended``: ``cap``, ``budget``, ``room`` or
    ``extent``; ``""`` for an explicit block);
    for a shard program's chunk also
    ``overlap``, each sharded mesh axis with the core span the
    core/shell split took there or why it took none.  ``hoisted`` names
    the scratch vars read as arrays filled once, ``hoist_kept`` the
    step-invariant ones left in-tile (``name:reason``), comma-joined,
    ``""`` where none."""
    return {"block": "x".join(str(b) for b in tiling["block"].values()),
            "hoisted": ",".join(tiling["hoisted"]),
            "hoist_kept": ",".join(
                f"{n}:{why}" for n, why in tiling["hoist_kept"].items()),
            "tile_mib": round(tiling["tile_bytes"] / 2 ** 20, 2),
            "budget_mib": round(tiling["budget"] / 2 ** 20, 2),
            "live_factor": tiling["live_factor"],
            "margin_overhead": tiling["margin_overhead"],
            "scratch_overhead": tiling["scratch_overhead"],
            "edge_overhead": tiling["edge_overhead"],
            "overshoot": "x".join(
                str(o) for o in tiling["overshoot"].values()),
            "overshoot_pad": "x".join(
                str(o) for o in tiling["overshoot_pad"].values()),
            "lane_fill": tiling["lane_fill"],
            "stages": tiling["stages"],
            "reach": "x".join(str(r) for r in tiling["reach"].values()),
            "stage_consumed": ",".join(
                "x".join(str(c) for c in cons.values())
                for cons in tiling["stage_consumed"]),
            "scoped_need_mib": round(
                tiling["scoped_need_bytes"] / 2 ** 20, 2),
            "vinstr_est": tiling["vinstr_est"],
            "growth_ended": tiling["growth_ended"] or "",
            "ops_per_point": tiling["ops_per_point"],
            "dag_ops_per_point": tiling["dag_ops_per_point"],
            "eval": tiling["eval"],
            "strip": "x".join(str(n) for n in tiling["strip"]),
            "strips": tiling["strips"],
            "strip_vregs": tiling["strip_vregs"],
            "fetch_windows": ",".join(
                f"{slot}:" + "x".join(str(hi - lo)
                                      for lo, hi in win.values())
                for slot, win in tiling["fetch_windows"].items()),
            "fetch_skipped": len(tiling["fetch_skipped"]),
            "fetch_bytes_per_step": tiling["fetch_bytes_per_step"],
            "write_bytes_per_step": tiling["write_bytes_per_step"],
            **({"overlap": ",".join(
                f"{d}:" + ("{}-{}".format(*ax["core"]) if ax["taken"]
                           else "no ({})".format(ax["why"]))
                for d, ax in tiling["overlap"].items())}
               if tiling.get("overlap") else {})}


def push_eligible_vars(program) -> Dict[str, str]:
    """Per written non-scratch var: ``"ok"`` when its VMEM output tile
    can be PUSHED to its consumers inside the grid step (no input DMA,
    no write-back — the push-memory tile-graph fusion), else the reason
    it cannot.  THE single eligibility definition — the build, the
    pipeline planner, and the checker's explain pass all read it.

    A var is pushable exactly when every read of it anywhere in the
    program is a same-sub-step read of the value written this sub-step
    (step offset ``+step_dir`` — the read rides the kernel's
    ``computed`` dict, never a ring tile), its writes are unconditional
    over the full domain (so the in-kernel zero-seeded base tile is
    bit-equivalent to the HBM ghost-zero pads on every cell a consumer
    can reach), and it has at least one such reader (a never-read
    written var is a final OUTPUT — it must stay on the write-DMA
    path).  Full-dim, misc-free vars only: partial-dim write slabs and
    misc-pinned writes leave base cells the zero seed cannot
    reproduce."""
    ana = program.ana
    dims = ana.domain_dims
    sd = ana.step_dir
    # reads per var across EVERY equation (rhs + conditions, scratch
    # eqs included): step offsets seen anywhere in the program
    read_offs: Dict[str, set] = {}
    writers: Dict[str, List] = {}
    for eq in ana.eqs:
        name = eq.lhs.var_name()
        writers.setdefault(name, []).append(eq)
        for p in _eq_points(eq):
            read_offs.setdefault(p.var_name(), set()).add(
                p.step_offset())
    out: Dict[str, str] = {}
    for n in sorted(program.geoms):
        g = program.geoms[n]
        if not g.is_written or g.is_scratch:
            continue
        if g.domain_dims != dims:
            out[n] = ("partial-dim written var (zero-seeded base tile "
                      "cannot reproduce the repeated-write slab)")
            continue
        if any(kind == "misc" for _dn, kind in g.axes):
            out[n] = ("misc axes (unwritten misc slices would read the "
                      "zero seed instead of the HBM values)")
            continue
        offs = read_offs.get(n, set())
        if not offs:
            out[n] = "never read (final output stays on the DMA path)"
            continue
        if offs != {sd}:
            bad = sorted(o if o is not None else 0
                         for o in offs if o != sd)
            out[n] = (f"read at step offsets {bad} (ring/same-level "
                      "reads need the HBM ring state)")
            continue
        if any(eq.cond is not None or eq.step_cond is not None
               for eq in writers.get(n, [])):
            out[n] = ("conditional write (unselected cells keep the "
                      "base tile, which a pushed var seeds with zeros)")
            continue
        out[n] = "ok"
    return out


#: vector registers the value of one strip may take.  More than the 64
#: there are: a strip's fixed cost (a pipeline that fills and drains
#: around every strip) outweighs the spills of a value this size, and
#: the chip ran strips of 50-100 registers fastest (``PERF.md`` 6, PR 44)
_STRIP_VREGS = 96
#: rows a ref-to-ref copy (a carry strip, a staged output window, an
#: explicit result tile's seed) moves an iteration
_COPY_ROWS = 8
#: least lead rows of a strip whose rows share shifted windows (a
#: diagonal read): the cover window of n + span - 1 rows is shifted into
#: place once for the strip's n
_STRIP_ROWS = 8

#: ``jax.named_scope`` of the pad-band re-zeroing after a kernel launch
SCOPE_ZERO_PADS = "yt_zero_pads"


def kernel_name(program, fuse_steps: int, arm: str = "") -> str:
    """``yt_<solution>_r<radius>_k<K>[_<arm>]`` -- the name the Pallas
    call carries into the lowered module and the device trace."""
    import re
    soln = re.sub(r"\W+", "_", program.soln.get_name())
    rad = max(program.ana.fused_step_radius().values(), default=0)
    return f"yt_{soln}_r{rad}_k{fuse_steps}" + (f"_{arm}" if arm else "")


def build_pallas_chunk(program, fuse_steps: int = 1,
                       block: Optional[Tuple[int, ...]] = None,
                       interpret: bool = False,
                       vmem_budget: int = _INTERPRET_PLAN_BUDGET,
                       distributed: bool = False,
                       pipeline_dmas: Optional[bool] = None,
                       skew=None,
                       vinstr_cap: int = 100_000,
                       stream_unsharded: bool = False,
                       unsharded_dims=None,
                       plan_only: bool = False,
                       reasons: Optional[List[dict]] = None,
                       region: Optional[Dict[str, Tuple[int, int]]] = None,
                       push=False,
                       arm: str = "",
                       onto: bool = False,
                       reuse_evicted: bool = False,
                       _sizer_only: bool = False,
                       _tile_eval: bool = False,
                       _strip: Optional[Tuple[int, int]] = None):
    """Build ``chunk(state, t0) -> state`` advancing ``fuse_steps`` steps
    in one fused Pallas sweep.

    ``program`` must be planned with ``extra_pad`` ≥ the fused halo
    (radius × fuse_steps) in the leading dims — the runtime arranges this.
    Returns (chunk_fn, tile_bytes).  The chunk is ``chunk.merge(state,
    chunk.written(state, t0))``: ``written`` is the device half (the
    ring slots the kernel writes, ``min(K, slots)`` a written var, and
    nothing else), ``merge`` puts the input's other arrays beside them
    by reference.  A launch compiled alone compiles ``written``.

    With ``distributed=True`` the chunk is the per-shard inner kernel of
    the shard_map+pallas path: it takes a third argument ``offsets`` (an
    i32 vector of this shard's global origin per domain dim, traced from
    ``lax.axis_index``) and the zero-outside-domain mask uses GLOBAL
    coordinates — so points in exchanged shard ghosts update through the
    fused sub-steps while true physical boundaries stay zero. ``program``
    must then be the per-shard plan built with ``global_sizes`` (its
    ``global_last`` drives last_domain_index conditions).

    ``skew`` selects the streaming skewed-wavefront tiling: in the
    stream dim (``lead[-1]``, the innermost grid dim) a fused sub-step's
    compute region shifts left by the step radius instead of shrinking
    symmetrically, and the inter-tile boundary strips each sub-step
    needs from its already-computed neighbor — the tile of the previous
    sequential grid step — ride a persistent VMEM carry.  This removes
    BOTH the redundant margin recompute and the 2·r·K-wide halo DMA of
    the uniform shrink in that dim — the TPU-native answer to the
    reference's temporal blocking (``setup.cpp:863``,
    ``context.cpp:838``), whose phase coloring exists to create *thread*
    parallelism a sequential Pallas grid does not need.  Every other
    lead dim, and the lane-minor dim (Mosaic 128-lane window
    alignment), keeps the uniform shrink.  ``None`` = auto: the stream
    dim engages when its margin model says it pays
    (``skew_engaged_dims``); ``True`` or a list naming the stream dim =
    force it (raising when infeasible, or where the list names any
    other dim); ``False`` = uniform shrink.
    Distributed chunks may skew too, but only where the stream dim is
    UNSHARDED (``unsharded_dims`` / ``stream_unsharded``): the carry
    then never crosses a shard boundary and the radius×K ghost pads
    cover the skew margins whenever the profit gate engages (mR =
    r+E_sk ≤ r·K exactly when E_sk < (K−1)·r); a mesh-decomposed dim
    keeps the uniform shrink.

    Every planning decision (skew engage/reject, ladder fallback, block
    shrink, DMA-pipelining on/off) appends a structured reason code to
    ``reasons`` — surfaced through ``chunk.tiling["reasons"]`` and read
    by the static checker's explain pass.  ``plan_only=True`` stops
    after planning (no kernel is traced, nothing allocates) and returns
    the plan dict instead of ``(chunk, tile_bytes)``.

    ``region`` restricts the OUTPUT sub-range per leading dim to
    ``{dim: (lo, hi)}`` in interior coordinates: the grid covers only
    the restricted span, fetch margins are re-derived from the
    restricted origin, and the global-coordinate mask stays exact.
    This is the core/shell split primitive of the overlapped
    shard_pallas exchange schedule (the fused-chunk analog of the
    reference's interior/exterior MPI overlap, ``context.cpp:377-478``).
    Correctness contract for callers: only interior cells inside the
    region (plus ceil-coverage window overshoot, whose values are NOT
    valid) are written — the scheduler must patch every cell outside
    the region from another chunk's output before use.  A restricted
    dim that is some written var's sublane axis must have a
    ``sub_t``-aligned ``lo`` (output DMA windows keep 8-aligned
    offsets on real Mosaic — raises otherwise), and restricted dims
    never skew (their carry geometry assumes the full span).

    ``onto`` makes the launch write INTO arrays the caller hands it:
    ``written(state, t0, offsets, base)`` takes ``base``, ``{name: [the
    min(K, slots) arrays]}`` of the padded shape, aliases them to its
    outputs (``input_output_aliases``: they ride behind the inputs and
    nothing reads them) and returns them with the launch's windows
    written over.  Every cell no window writes keeps the caller's
    value.

    With a ``region``, ``base`` is what another build's ``written``
    returned: a shell lands in the core's output where it belongs, no
    full-size output of its own and no merge copy (four shells of a 2x2
    shard were 11 GiB of them, more than the chip holds).  A restricted
    dim's block must then divide its span (no ceil-coverage overshoot
    inside the interior: the block is snapped down to a divisor, and
    the build raises where none rides the sublane tile) and every
    written var must have every restricted dim.

    Without one (the whole interior: the one-chip launch loop,
    ``runtime/context.py _PallasLaunch``), ``base`` is any arrays whose
    lead-dim pad bands are zero -- the ring slots an earlier launch
    evicted, dead since it ended -- and what they hold elsewhere is
    never seen: the launch writes every interior cell, and every window
    cell outside the global problem (ceil overshoot, a skewed level's
    shift) it writes as the zero the kernel masked it to, so the bands
    are zero after it in every lead dim, overshoot or not, and
    ``written`` sets nothing.  Donated to an executable of ``written``,
    ``base`` is the outputs' memory: the launch allocates nothing.

    ``reuse_evicted`` writes a new level ONTO the ring slot it evicts
    wherever the kernel never reads that slot (``fetch_skipped``: no
    stage reads it, so no input DMA is started for it; a slot
    ``analysis.kept_vars`` keeps is fetched and stays out of place):
    the slot's operand is aliased to the output
    (``input_output_aliases``), so inside a program of its own the new
    level takes the buffer the ring gives up and a ring that only
    rotates (``[s0, s1] -> [s1, new]``) moves nothing: the operand
    would otherwise hold the slot live through the call, and the
    compiler could not give its buffer to the output.  Every cell no
    output window writes holds the evicted level's stale values as it
    would a fresh output's garbage: the lead-dim pad bands are re-zeroed
    by ``written`` all the same.  For callers whose program drops the
    evicted slot after the launch (the shard path's whole-shard chunk);
    a launch compiled alone must not ask for it: the state it reads is
    not donated, and the compiler would copy each aliased slot first
    (it writes ``onto`` the slots the launch BEFORE it evicted
    instead).
    ``chunk.tiling["reused"]`` names the slots taken, ``"var/slot"``.

    ``_sizer_only`` stops where the default block would be planned and
    returns the accounting that prices a candidate
    (:func:`block_sizer`).  ``_tile_eval`` builds the whole-tile
    evaluator where the strip evaluator would run (the tests' other
    side; the push arm always takes it), and ``_strip`` fixes the
    strip's shape (lead rows, sublane rows) where the build would
    compute one.

    The kernel is named by the program, not by whatever jit calls the
    wrapper: ``yt_<solution>_r<radius>_k<K>`` plus ``_<arm>`` where the
    caller states the build's role (the shard path's ``core`` and
    ``shell`` calls).  A device trace finds the program's kernels by
    that name (:func:`kernel_name`).

    ``push`` selects the push-memory tile-graph fusion: an eligible
    intermediate var's VMEM output tile is consumed by its reader
    stages inside the grid step (the kernel's ``computed`` dict already
    carries it) and the var leaves BOTH HBM paths — its input tiles are
    never DMA'd in and its outputs never written back, so each K-group
    saves one full read + one full write of the var (the pipeline HBM
    model's 48→24 bytes/pt halving on the RTM chain).  Eligibility is
    :func:`push_eligible_vars` (every read program-wide at step offset
    ``+step_dir``, unconditional full-dim misc-free writes, ≥ 1
    reader); distributed builds decline (scope: single device).
    ``False`` = off (the default — a pushed var's HBM ring goes STALE,
    so plain solutions keep every var observable); ``None`` =
    auto-engage every eligible var (the pipeline runtime's fused
    path); ``True`` = force (raises when nothing is eligible); a list
    = force exactly those vars (raising on any ineligible name).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ana = program.ana
    dims = ana.domain_dims
    K = fuse_steps
    if reasons is None:
        reasons = []
    from yask_tpu.compiler.expr import uses_misc_index
    has_misc_value = any(
        uses_misc_index(eq.rhs, eq.cond, eq.step_cond) for eq in ana.eqs)
    lead = dims[:-1]
    minor = dims[-1]

    # Within one fused sub-step a stage has consumed the longest chain of
    # dependent reads that ends in it (the accounting of the reference's
    # temporal blocking, setup.cpp:863); the full-step shrink
    # per dim is the largest of them and the fused halo K x that
    # (fused_step_radius is the single source both here and in the
    # runtime's pad planning).
    nstages = len(ana.stages)
    stage_consumed = ana.stage_consumed()
    rad_all = ana.fused_step_radius()
    rad = {d: rad_all.get(d, 0) for d in lead}
    hK = {d: rad[d] * K for d in lead}

    sizes = {d: program.sizes[d] for d in dims}

    # Streaming skew rides the innermost grid dim (the one consecutive
    # sequential grid steps advance by +1, so the VMEM carry written by
    # step i is what step i+1 patches in).
    sdim = lead[-1] if lead else None
    ring_read_vars = set()
    for sr_ in program.stage_reads:
        ring_read_vars.update(sr_.keys())
    from yask_tpu.compiler.lowering import tpu_tile_dims
    sub_t, _lane_t = tpu_tile_dims(program.dtype)

    # ---- region restriction (core/shell split) -------------------------
    # reg_lo shifts every window origin; span replaces sizes[d] in the
    # grid/coverage math.  The minor dim always rides whole (lane-axis
    # windows cannot restrict), and a restricted dim that is a written
    # var's sublane axis needs a sub_t-aligned lower bound or the output
    # DMA offsets become 8-unaligned — a hardware-only crash the CPU
    # interpreter cannot catch, so it is rejected statically here.
    region = dict(region) if region else {}
    for d, bounds in region.items():
        if d not in lead:
            raise YaskException(
                f"region restriction on '{d}' is not a leading domain "
                f"dim of this solution ({lead}); the minor (lane) dim "
                "always rides whole")
        lo_, hi_ = bounds
        if not (0 <= lo_ < hi_ <= sizes[d]):
            raise YaskException(
                f"region ({lo_},{hi_}) in dim '{d}' outside the "
                f"interior [0,{sizes[d]})")
    reg_lo = {d: region.get(d, (0, sizes[d]))[0] for d in lead}
    span = {d: (region.get(d, (0, sizes[d]))[1]
                - region.get(d, (0, sizes[d]))[0]) for d in lead}
    restricted = {d for d in lead
                  if (reg_lo[d], span[d]) != (0, sizes[d])}
    if restricted:
        sub_constrained = set()
        for g_ in program.geoms.values():
            if g_.is_scratch or len(g_.axes) < 2:
                continue
            dn_, kind_ = g_.axes[-2]
            if kind_ == "domain" and dn_ != minor:
                sub_constrained.add(dn_)
        for d in restricted & sub_constrained:
            if reg_lo[d] % sub_t != 0:
                raise YaskException(
                    f"region lower bound {reg_lo[d]} in dim '{d}' is "
                    f"not a multiple of the sublane tile {sub_t}: "
                    "output DMA windows would be 8-unaligned on real "
                    "Mosaic (align the core/shell split boundaries)")
        reasons.append({"code": "region_restricted",
                        "region": {d: list(region[d])
                                   for d in sorted(restricted)}})
    if onto:
        lacking = sorted(
            n for n, g_ in program.geoms.items()
            if g_.is_written and not g_.is_scratch
            and not restricted <= set(g_.domain_dims))
        if lacking:
            raise YaskException(
                "a launch writes onto another's arrays only inside a "
                f"region every written var has the dims of (not {lacking})")
    # carry depth per var = its ring allocation (an upper bound on how
    # many sub-steps back its levels are read).  The per-level write
    # windows shift by r per sub-step; the stream dim is the sublane
    # (tiled) axis of every full-dim var, so its HBM write windows must
    # keep 8-aligned offsets.  Sublane-multiple radii (r=8 fp32) shift
    # exactly; other radii round the shift DOWN to the sublane tile and
    # widen the window by one tile (E_sk extra computed width on the
    # right makes the widened span valid; consecutive sequential tiles
    # overwrite the sub_t-wide overlap with identical valid values).
    elig_dims = skew_eligible_dims(program, K)
    E_all = skew_extra_widths(program, K)
    # Distributed chunks may skew only where the stream dim is UNSHARDED
    # (asserted by the shard planner): the carry strips then never cross
    # a shard boundary, each shard spans its full extent, and the r·K
    # ghost pads already cover the skew margins K·r (left) and r+E_sk
    # (right, ≤ (K−1)·r whenever the profit gate engages).  This is the
    # distributed temporal-blocking analog of the reference's
    # rank-level wave-fronts (setup.cpp:863).
    if unsharded_dims is None:
        if not distributed:
            unsharded_dims = set(lead)
        else:
            unsharded_dims = ({sdim} if (stream_unsharded
                                         and sdim is not None) else set())
    unsharded_dims = set(unsharded_dims)
    # restricted dims never skew: their carry buffers and shifted write
    # windows assume the full span.  (In the distributed overlap split
    # this is automatic — restricted dims are the sharded dims — but a
    # direct caller could combine them; removing them from the eligible
    # set makes forced skew on a restricted dim raise below.)
    unsharded_dims -= restricted

    if isinstance(skew, (list, tuple, set, frozenset)) and not skew:
        skew = False   # an explicit empty dim list = uniform shrink
    forced = skew is True or isinstance(skew, (list, tuple, set,
                                               frozenset))
    if skew is None:
        # Auto-engage per the shared per-dim profit gate (the r4
        # cube-wavefront proxy regression came from engaging
        # unprofitable misaligned small radii); explicit skew still
        # forces the path for A/B measurement.
        skew_dims = skew_engaged_dims(program, K,
                                      unsharded=unsharded_dims)
    elif skew is False:
        skew_dims = []
    elif skew is True:
        skew_dims = [sdim] if sdim is not None else []
    else:
        want = set(skew)
        skew_dims = [d for d in lead if d in want]
        if len(skew_dims) != len(want):
            raise YaskException(
                f"skew dims {sorted(want - set(skew_dims))} are not "
                f"leading domain dims of this solution ({lead})")
    if forced:
        bad = [d for d in skew_dims
               if d not in elig_dims or d not in unsharded_dims]
        if bad or not skew_dims:
            raise YaskException(
                f"skewed wavefront needs K >= 2, an unsharded stream "
                f"dim (carry strips cannot cross shard boundaries) of "
                f"radius > 0 (only lead[-1] can skew), and all written "
                f"vars spanning every domain "
                f"dim; got K={K}, requested={skew_dims or skew}, "
                f"eligible={elig_dims}, distributed={distributed}, "
                f"unsharded={sorted(unsharded_dims)}, partial-written="
                f"{sorted(g.name for g in program.geoms.values() if g.is_written and not g.is_scratch and g.domain_dims != dims)}")
    use_skew = bool(skew_dims)
    skew_set = set(skew_dims)
    # Structured reason codes for the skew decision (explain pass): one
    # per leading dim under auto-engage, one summary line when forced or
    # disabled.  Codes, not prose, so tools can branch on them.
    if skew is None:
        for d in lead:
            if d in skew_set:
                reasons.append({
                    "code": "skew_engaged", "dim": d,
                    "detail": f"profit gate ({K}+1)*{rad[d]}"
                              f"+{E_all.get(d, 0)} < 2*{K}*{rad[d]}"})
            elif d in elig_dims and d in unsharded_dims:
                reasons.append({
                    "code": "skew_gate_rejected", "dim": d,
                    "detail": f"({K}+1)*{rad[d]}+{E_all.get(d, 0)} >= "
                              f"2*{K}*{rad[d]}"})
            else:
                why = ("not the stream dim" if d != sdim
                       else "mesh-decomposed (carry cannot cross shards)"
                       if d not in unsharded_dims else
                       "ineligible (K<2, radius 0, or partial-dim "
                       "written vars)")
                reasons.append({"code": "skew_ineligible", "dim": d,
                                "detail": why})
    elif forced:
        reasons.append({"code": "skew_forced", "dims": list(skew_dims)})
    else:
        reasons.append({"code": "skew_disabled",
                        "detail": "skew=False requested"})

    # ---- push-memory resolution ----------------------------------------
    # Same gate shape as skew: False = off, None = auto-engage
    # every eligible var, True/list = force (raise when infeasible).
    # Pushed vars leave BOTH HBM paths (no input DMA, no write-back);
    # their rings in the returned state are STALE — only the pipeline
    # runtime, which hides bound intermediates, turns this on.
    push_req = push
    if isinstance(push, (list, tuple, set, frozenset)) and not push:
        push = False
    push_forced = push is True or isinstance(push, (list, tuple, set,
                                                    frozenset))
    pushed: List[str] = []
    if push is False:
        reasons.append({"code": "push_disabled",
                        "detail": "push=False requested"})
    else:
        push_block = ("distributed build (scope: single device)"
                      if distributed else None)
        elig_push = ({} if push_block is not None
                     else push_eligible_vars(program))
        if push_forced:
            want_p = (sorted(n for n, why in elig_push.items()
                             if why == "ok")
                      if push is True else sorted(set(push)))
            bad_p = [n for n in want_p
                     if elig_push.get(n, "not a written non-scratch "
                                      "var of this program") != "ok"]
            if push_block is not None or bad_p or not want_p:
                if push_block is not None:
                    why_p = push_block
                elif bad_p:
                    why_p = "; ".join(
                        f"'{n}': {elig_push.get(n, 'unknown var')}"
                        for n in bad_p)
                else:
                    why_p = f"no eligible vars (candidates: {elig_push})"
                raise YaskException(
                    f"push-memory fusion infeasible: {why_p}")
            pushed = want_p
            reasons.append({"code": "push_forced", "vars": list(pushed)})
        else:   # auto
            if push_block is not None:
                reasons.append({"code": "push_ineligible",
                                "detail": push_block})
            else:
                for n in sorted(elig_push):
                    if elig_push[n] == "ok":
                        pushed.append(n)
                        reasons.append({"code": "push_engaged",
                                        "var": n,
                                        "detail": "all reads at "
                                                  "+step_dir ride the "
                                                  "in-step computed "
                                                  "tile"})
                    else:
                        reasons.append({"code": "push_ineligible",
                                        "var": n,
                                        "detail": elig_push[n]})
    pushed_set = set(pushed)
    use_push = bool(pushed)

    R = dict(rad)
    # Misaligned (non-sublane-multiple) stream radii: every skewed
    # region carries E_sk extra computed width on its right so the
    # sublane-rounded write windows (shift floored to sub_t, size
    # +sub_t) stay inside the level's valid span: need E ≥ d + sub_t
    # with d = shift−floor(shift) < sub_t ⇒ 2·sub_t suffices.
    E = {d: E_all[d] if d in skew_set else 0 for d in lead}
    # per-dim tile margins from THE dataflow plan: uniform shrink =
    # radius×K both sides; a skewed dim keeps K·r on the left (write
    # regions shift left by r per sub-step) but only r (+E_sk) on the
    # right
    tplan = TilePlan(program, K, skew_dims=skew_dims, e_sk=E)
    mL, mR = tplan.margins()

    # Every var's leading-dim pads must cover the fused halo, or the DMA
    # start/end would clamp silently and corrupt results: the runtime
    # plans extra_pad = radius*K at prepare time, so a K larger than
    # planned must be rejected here (the auto-tuner relies on this to
    # skip infeasible candidates).
    # (pricing a candidate needs no pads: the tuner's seed is planned
    # on the global program before any K's pads exist)
    for n, g in ({} if _sizer_only else program.geoms).items():
        if n in pushed_set:
            continue  # pushed vars have no HBM DMA windows to cover
        for d in lead:
            if d not in g.domain_dims:
                continue  # partial-dim var lacks this axis
            pl_, pr_ = g.pads[d]
            if pl_ < mL[d] or pr_ < mR[d]:
                raise YaskException(
                    f"pallas fuse_steps={K} needs pad >= {mL[d]} in dim "
                    f"'{d}' but var '{n}' has ({pl_},{pr_}); re-prepare "
                    "with wf_steps set to the desired fusion depth")

    # an explicit block is taken as given; the default one is planned
    # further down, once the accounting it is priced by is defined
    block_arg = tuple(block) if block is not None else None
    explicit_block = block is not None
    block = ({d: min(b, span[d]) for d, b in zip(lead, block)}
             if explicit_block else {})

    # ---- Mosaic DMA slab geometry ---------------------------------------
    # HBM memrefs carry a tiled (sublane×lane) layout; DMA windows must
    # have tile-aligned sizes AND offsets on the last two physical axes
    # (probed on TPU v5e). The lane axis of every DMA-able var is the
    # solution minor (pallas_applicable) and rides WHOLE — VarGeom pads
    # its total to a 128-multiple. Each var's sublane axis gets an
    # 8-aligned window: the static part of the slab start is rounded
    # down, the residual becomes a static in-tile shift, and the slab
    # size is rounded up (VarGeom's sublane slack guarantees room).
    # The slab is what a var's VMEM tile is shaped by and what every
    # region and read index is counted in; what an input DMA copies of
    # it is the (var, slot)'s fetch window (further down, once the
    # strip plan says which evaluator runs): the rows the stage chain
    # reads of that slot, rounded out to the same 8 rows on the
    # sublane axis -- a slab starts on the tile, so a window of it
    # does -- and any rows at all on an untiled lead axis.
    def _sub_dim(g):
        """The var's sublane (2nd-last physical) axis, when it is a lead
        domain dim (the constrained window case)."""
        if len(g.axes) >= 2:
            dn, kind = g.axes[-2]
            if kind == "domain" and dn != minor:
                return dn
        return None

    # an array only the hoisted scratch vars' fill touches
    # (``analysis.derive_only``: tti's theta and phi) is no operand
    # of the kernel: no buffer, no DMA, no window
    unread = ana.derive_only
    for n in ana.hoisted:
        reasons.append({"code": "scratch_hoisted", "var": n,
                        "detail": "step-invariant: read as an array "
                                  "filled once, not evaluated in-tile"})
    for n, why in ana.hoist_kept.items():
        reasons.append({"code": "scratch_hoist_kept", "var": n,
                        "detail": f"step-invariant, left in-tile: {why}"})
    non_scratch_geoms = [g for g in program.geoms.values()
                         if not g.is_scratch and g.name not in unread]
    # pushed vars have no HBM windows: they neither constrain the
    # right-edge overshoot nor the pad coverage (block sublane
    # alignment still honors every non-scratch geom — conservative)
    window_geoms = [g for g in non_scratch_geoms
                    if g.name not in pushed_set]

    def _goff(d):
        """Interior-coordinate offset of tile position 0 relative to
        pid·block."""
        return reg_lo[d] - mL[d]

    def _gcount(d, b):
        """Grid extent in dim d: ceil coverage of the (possibly
        region-restricted) span; a skewed dim needs (K−1)·r more
        tiles on the right because the final-level write regions sit
        shifted left by (K−1)·r (skew and region are disjoint)."""
        sp = span[d] + ((K - 1) * R[d] if d in skew_set else 0)
        return -(-sp // b)

    def _slab_geom(g, d, b):
        """(base, resid, slab_size) of dim-d windows for var g at block
        size b (window origins shift by the region's lower bound)."""
        s = g.origin[d] + _goff(d)
        if _sub_dim(g) == d:
            base = (s // sub_t) * sub_t
            r = s - base
            sz = -(-(b + mL[d] + mR[d] + r) // sub_t) * sub_t
        else:
            base, r, sz = s, 0, b + mL[d] + mR[d]
        return base, r, sz

    def _window_end(g, d, b):
        """Where var g's last dim-d DMA window ends, in rows of its
        allocation: ceil-coverage grids let the right-edge window run
        into the right pad."""
        base, _r, sz = _slab_geom(g, d, b)
        return (_gcount(d, b) - 1) * b + base + sz

    def _overshoot_ok(d, b):
        """Every var's allocation must contain its right-edge
        window."""
        for g in window_geoms:
            if d not in g.domain_dims:
                continue
            if g.origin[d] + _goff(d) < 0:
                return False
            if _window_end(g, d, b) > g.shape[g.axis_of(d)]:
                return False
        return True

    # where the last window of every block ``_fit_block`` let through
    # ended, at its furthest: what prepare sizes a lead dim's right pad
    # by (``StencilContext._pallas_pad_needs`` plans on pads with room
    # to spare, where nothing is shrunk, so that on the pads it then
    # grants every block of the same sequence is let through again)
    window_reach: Dict[Tuple[str, str], int] = {}

    def _fit_block(d, b):
        b = _fitted(d, b)
        for g in window_geoms:
            if d in g.domain_dims:
                window_reach[g.name, d] = max(
                    window_reach.get((g.name, d), 0),
                    _window_end(g, d, b))
        return b

    def _fitted(d, b):
        sub = any(_sub_dim(g) == d for g in non_scratch_geoms)
        step = sub_t if sub else 1
        b = max(step, min(b, span[d]))
        if sub:
            b = max(step, (b // step) * step)
        while b > step and not _overshoot_ok(d, b):
            b -= step
        if onto and d in restricted:
            # no ceil-coverage overshoot inside the interior: what lies
            # beside the region is another launch's
            while b > step and span[d] % b:
                b -= step
            if span[d] % b:
                raise YaskException(
                    f"no block in dim '{d}' divides the region's span "
                    f"{span[d]} in steps of {step}: the launch cannot "
                    "write onto another's arrays")
        if not _overshoot_ok(d, b):
            raise YaskException(
                f"no feasible pallas block in dim '{d}': pads too small "
                "for DMA slab rounding; re-prepare with larger wf_steps "
                "pads or different block sizes")
        return b

    def _fallback(cause: str):
        """Auto-engaged skew that turned out infeasible falls back to
        the uniform shrink rather than failing a configuration that
        still fits, and records a structured reason."""
        reasons.append({
            "code": "skew_fallback", "cause": cause,
            "from_dims": list(skew_dims), "to": "uniform shrink"})
        return build_pallas_chunk(
            program, fuse_steps=fuse_steps, block=block_arg,
            interpret=interpret, vmem_budget=vmem_budget,
            distributed=distributed, pipeline_dmas=pipeline_dmas,
            skew=False,
            vinstr_cap=vinstr_cap, stream_unsharded=stream_unsharded,
            unsharded_dims=unsharded_dims,
            plan_only=plan_only, reasons=reasons, region=region or None,
            push=push_req, arm=arm, onto=onto,
            reuse_evicted=reuse_evicted, _tile_eval=_tile_eval,
            _strip=_strip)

    var_order = [n for n in sorted(program.geoms)
                 if not program.geoms[n].is_scratch and n not in unread]
    written = [n for n in var_order if program.geoms[n].is_written]
    scratch_vars = [n for n in sorted(program.geoms)
                    if program.geoms[n].is_scratch]
    # vars with no domain dims (scalars, misc-only parameter tables) ride
    # SMEM and are read by static scalar indexing — no DMA, no VMEM tile
    smem_vars = {n for n in var_order
                 if not program.geoms[n].domain_dims}
    # pushed vars ride neither DMA path: no input fetch (consumers read
    # the in-step computed tile) and no write-back (their HBM rings go
    # stale — the pipeline runtime hides them)
    dma_vars = [n for n in var_order
                if n not in smem_vars and n not in pushed_set]
    written_out = [n for n in written if n not in pushed_set]

    base_off: Dict[Tuple[str, str], int] = {}
    resid: Dict[Tuple[str, str], int] = {}
    slab: Dict[Tuple[str, str], int] = {}

    def _plan_slabs():
        base_off.clear()
        resid.clear()
        slab.clear()
        for n, g in program.geoms.items():
            for d in g.domain_dims:
                if d == minor:
                    continue
                if g.is_scratch or n in pushed_set:
                    # scratch and pushed tiles never touch HBM:
                    # unconstrained (no DMA window alignment)
                    base_off[n, d], resid[n, d] = 0, 0
                    slab[n, d] = block[d] + mL[d] + mR[d]
                else:
                    base_off[n, d], resid[n, d], slab[n, d] = \
                        _slab_geom(g, d, block[d])

    # tile geometry per var (its own axes): lead dims are DMA slabs, the
    # minor (lane) dim and misc axes ride their whole padded extents
    def tile_shape(name):
        g = program.geoms[name]
        shp = []
        for i, (dn, kind) in enumerate(g.axes):
            if kind == "misc" or dn == minor:
                shp.append(g.shape[i])
            else:
                shp.append(slab[name, dn])
        return tuple(shp) if shp else (1,)  # 0-dim vars ride as (1,)

    dtype = program.dtype
    esize = jnp.dtype(dtype).itemsize
    slots: Dict[str, int] = {}
    for n in var_order:
        slots[n] = len(program_state_slots(program, n))

    # skewed-wavefront carry: per ring-read written var, the
    # (D+1)·r-wide boundary strips of levels 1..K−1 along the stream
    # dim that the neighboring tile patches in.  Single-buffered: a
    # level's strip is saved at the top of the LAST sub-step that
    # patches it, AFTER the patches — so the reader's final read of a
    # slot precedes the overwrite.  The reader is the very next
    # sequential grid step (one strip).
    # Carry EVERY written var that is read back at all — not just the
    # offset-read set (``stage_reads`` omits pure same-point reads, but
    # a same-point consumer at the next sub-step still reads the slid
    # region's left strip, which only the neighboring tile computed:
    # awp's anelastic memory vars corrupted a radius-wide band when
    # they were left out of the carry).
    # Pushed vars never carry: their only reads are same-sub-step
    # ``computed`` reads, which never touch the ring tiles the carry
    # strips patch.
    carry_vars = ([n for n in written
                   if (n in ring_read_vars
                       or n in ana.read_var_names())
                   and n not in pushed_set]
                  if use_skew else [])
    carr_base: Dict[str, int] = {}   # var -> its carry's scratch index
    for _n in carry_vars:
        # vars without the stream dim (misc-only SMEM riders) have no
        # strip geometry in it — their values are domain-independent
        # and recomputed identically by every tile
        if any(dn == sdim for dn, _k in program.geoms[_n].axes):
            carr_base[_n] = len(carr_base)

    def carry_shape(name):
        """One strip of ``(slots + 1)·r`` rows of the stream dim a
        carried level (levels 1..K−1), every other axis the tile's."""
        shp = list(tile_shape(name))
        g = program.geoms[name]
        ax = [i for i, (dn, _k) in enumerate(g.axes) if dn == sdim][0]
        shp[ax] = (slots[name] + 1) * R[sdim]
        return (max(K - 1, 1),) + tuple(shp)

    def _result_bytes():
        """One result tile per written var: the unit the capability
        table's live-value model counts Mosaic's live values in."""
        return sum(int(math.prod(tile_shape(n))) * esize
                   for n in written)

    def _in_place_vars():
        """Written vars whose strips are stored straight into the
        evicted ring slot's buffer: one equation writes the var, and
        every read of that slot anywhere in the program is that
        equation's, at the point it writes (iso3dfd's ``p(t-1)``).  Any
        other var gets an explicit result tile, seeded with the evicted
        slot's."""
        nwriters: Dict[str, int] = {}
        for eq in ana.eqs:
            nwriters[eq.lhs.var_name()] = \
                nwriters.get(eq.lhs.var_name(), 0) + 1
        ok = {n: nwriters.get(n, 0) == 1 for n in written}
        for eq in ana.eqs:
            for pt in _eq_points(eq):
                n = pt.var_name()
                if n not in ok:
                    continue
                so = pt.step_offset()
                idx = (slots[n] - 1 if so is None
                       else slots[n] - 1 + so * ana.step_dir)
                if idx == 0 and (
                        eq.lhs.var_name() != n
                        or any(pt.domain_offsets().values())):
                    ok[n] = False
        return ok

    # THE live-value model (capability table): Mosaic's scoped need for
    # a candidate's tiles, against the class's room.  Where the chip
    # has measured the class this — not the budget alone — is what
    # decides shrinking and pipelining; unmeasured, the need is twice
    # the tiles and the default budget half the limit, as before.
    from yask_tpu.backend import get_capability
    _cap = get_capability()
    _stages = len(ana.stages)
    _nscratch = len(scratch_vars)
    _room = _cap.vmem_room_bytes(K, _stages, _nscratch)
    _row = _cap.vmem_live_row(K, _stages, _nscratch)
    _measured = _row is not None
    # a row read off the strip kernel prices the buffers that kernel
    # declares: a result tile only for a var whose strips cannot go
    # into the ring slot it evicts.  The whole-tile evaluator (the push
    # arm; a solution with no lead dim) holds every written var's
    # result as a value, and is counted as it was
    _declared = bool(_measured and _row.declared and lead
                     and not (_tile_eval or use_push))
    _in_place = _in_place_vars()
    _res_tiles = ([n for n in written if not _in_place[n]]
                  if _declared else written)

    def _tile_bytes():
        in_b = sum(slots[n] * int(math.prod(tile_shape(n))) * esize
                   for n in dma_vars)
        # workspace for sub-step results (one tile per written var, or,
        # priced as declared, per explicit result tile) and the in-tile
        # scratch values
        work_b = sum(int(math.prod(tile_shape(n))) * esize
                     for n in _res_tiles)
        work_b += sum(int(math.prod(tile_shape(n))) * esize
                      for n in scratch_vars)
        # pushed vars have no DMA scratch refs, but their ring values
        # (zero seed → rotated computed tiles) stay LIVE across the
        # sub-steps — one tile per slot, in the work accounting (they
        # never double-buffer, so the pipe model must not 2× them)
        work_b += sum(slots[n] * int(math.prod(tile_shape(n))) * esize
                      for n in pushed)
        work_b += sum(int(math.prod(carry_shape(n))) * esize
                      for n in carr_base)
        return in_b, work_b

    def _need(tile_b):
        return _cap.vmem_need_bytes(K, _stages, tile_b, _result_bytes(),
                                    _nscratch)

    def _over(tile_b):
        """``tile_b`` bytes of tiles do not fit: over the tile budget,
        or their modelled scoped need over the class's room (only a
        measured class can fail the second with a budget at or under
        its default)."""
        return tile_b > vmem_budget or (
            _measured and _need(tile_b) > _room)

    def _refuse(what: str, tile_b: int, advice: str):
        """Raise for tiles that do not fit, saying which bar they
        missed; ``.vmem`` carries the numbers for the checker."""
        e = YaskException(
            f"{what} {tile_b/2**20:.1f} MiB VMEM (budget "
            f"{vmem_budget/2**20:.0f}; Mosaic's modelled scoped need "
            f"{_need(tile_b)/2**20:.1f} of {_room/2**20:.1f} MiB); "
            f"{advice}")
        e.vmem = {"tile_bytes": tile_b, "vmem_budget": vmem_budget,
                  "result_bytes": _result_bytes(),
                  "scoped_need_bytes": _need(tile_b),
                  "vmem_room": _room,
                  "over": "budget" if tile_b > vmem_budget else "room"}
        raise e

    def stage_region(k, si):
        """The region stage ``si`` of fused sub-step ``k`` evaluates,
        ``(lo, hi)`` by dim in tile coordinates.  The margin eaten by
        then in a lead dim: every earlier sub-step consumed ``rad``
        whole, this one the longest chain of dependent reads that ends
        in the stage (``analysis.stage_consumed``), its own included."""
        region = []
        for d in lead:
            if d in skew_set:
                # skew: fixed-width region sliding left by r per
                # sub-step; stages still consume their margins.
                # E_sk extra right width (misaligned radii) rides
                # every region so the telescoping validity spans
                # keep covering the widened write windows.
                c_stage = stage_consumed[si][d]
                lo = mL[d] - (k + 1) * R[d] + c_stage
                region.append((lo, lo + block[d]
                               + 2 * (R[d] - c_stage) + E[d]))
            else:
                consumed = rad[d] * k + stage_consumed[si][d]
                region.append((consumed,
                               block[d] + mL[d] + mR[d] - consumed))
        # minor: interior-relative (per-var pad origin applied at
        # read/write time); pads stay zero
        region.append((0, sizes[minor]))
        return region

    def scratch_region(name, region):
        """Where a scratch var's eq is evaluated: its stage's region
        EXPANDED by the var's write-halo, the minor dim's too."""
        wh = ana.scratch_write_halo.get(name, {})
        sregion = []
        for di, d in enumerate(lead):
            wl, wr = wh.get(d, (0, 0))
            lo, hi = region[di]
            sregion.append((lo - wl, hi + wr))
        wl_m, wr_m = wh.get(minor, (0, 0))
        sregion.append((-wl_m, sizes[minor] + wr_m))
        return sregion

    # operations a point of every equation, by stage, with the scratch
    # var it writes (None: a final equation, evaluated on the stage's
    # own region): the operations the evaluation memo emits for it, a
    # node that several trees hold counted once, in the equation that
    # reaches it first, under the memo's own scope (one a part; one an
    # equation of a scratch part or of a part that reads a misc index as
    # a value).  What ``_vinstr_est`` multiplies; their sum is the
    # row's ``dag_ops_per_point``, beside the trees' own sum
    # (``ops_per_point``), which is held to nothing
    from yask_tpu.compiler.expr import (CounterVisitor,
                                        DagCounterVisitor,
                                        uses_misc_index)
    _eq_ops: List[List[Tuple[Optional[str], int]]] = []
    ops_per_point = 0
    for _stage in ana.stages:
        _eq_ops.append([])
        for _part in _stage.parts:
            _own_memo = _part.is_scratch or any(
                uses_misc_index(_eq.rhs, _eq.cond, _eq.step_cond)
                for _eq in _part.eqs)
            _seen: set = set()
            for _eq in _part.eqs:
                _cv = CounterVisitor(sincos_args=ana.sincos_args)
                _eq.accept(_cv)
                ops_per_point += _cv.num_ops
                if _own_memo:
                    _seen = set()
                _dv = DagCounterVisitor(sincos_args=ana.sincos_args,
                                        seen=_seen)
                _eq.accept(_dv)
                _eq_ops[-1].append(
                    (_eq.lhs.var_name() if _part.is_scratch else None,
                     _dv.num_ops))
    dag_ops_per_point = sum(ops for _st in _eq_ops for _n, ops in _st)

    def _stage_regions():
        """``(stage index, region)`` of every stage of every fused
        sub-step, at the block the accounting points at."""
        for k in range(K):
            for si in range(nstages):
                yield si, stage_region(k, si)

    def _vinstr_est():
        """Estimated Mosaic vector instructions of this kernel at the
        block the accounting points at: the operations a point the
        evaluation memo emits for each equation (``_eq_ops``: a shared
        node once), times the vector registers of the region the kernel
        evaluates it on (a scratch var's grown by its write halo), over
        every stage of every fused sub-step.  What ``vinstr_cap`` is
        held against (``plan_blocks``)."""
        est = 0
        for si, reg in _stage_regions():
            for name, ops in _eq_ops[si]:
                ext = [hi - lo for lo, hi in (
                    scratch_region(name, reg) if name else reg)]
                ext[-1] = -(-ext[-1] // _lane_t)
                if len(ext) > 1:
                    ext[-2] = -(-ext[-2] // sub_t)
                est += ops * math.prod(ext)
        return est

    def _sized(cand):
        """Point the accounting at a candidate block: its tiles as this
        build would count them and its instruction estimate.  THE
        price ``plan_blocks`` grows by."""
        block.clear()
        block.update(cand)
        _plan_slabs()
        return BlockPrice(*_tile_bytes(), _result_bytes(),
                          _vinstr_est(), _declared)

    if _sizer_only:
        return _sized
    # which reading ended the default plan's growth: the planner's
    # last verdict (``cap``: the instruction estimate; ``budget``: the
    # declared tiles; ``extent``: nothing larger covers the span in
    # fewer tiles), or, where the build shrank the planner's block
    # back, the bar it was over: ``room``, the class's modelled scoped
    # need, or ``budget``; None for an explicit block
    growth_ended = None
    if not explicit_block:
        from yask_tpu.ops.tile_planner import plan_blocks
        # the skewed dim's floor (its carry) and margin model, read off
        # THE TilePlan (the auto-tuner's seed plan reads the same
        # object via skew_plan_hints)
        _trail: List[Dict] = []
        block.update(plan_blocks(
            program, fuse_steps=K, vmem_budget=vmem_budget,
            vinstr_cap=vinstr_cap, min_block=tplan.min_block(),
            margin_override=tplan.margin_override(), sizer=_sized,
            trail=_trail))
        growth_ended = _trail[-1]["verdict"]
    try:
        _block_req = dict(block)
        for d in lead:
            block[d] = _fit_block(d, block[d])
        if block != _block_req:
            # never a window outside an allocation; ``shrunk`` says by
            # how many rows (prepare pads a lead dim for the overshoot
            # of the block it expects, so a planner-chosen block that
            # loses rows here was planned on other pads)
            reasons.append({
                "code": "block_fitted", "from": _block_req,
                "to": dict(block),
                "shrunk": {d: _block_req[d] - block[d] for d in lead
                           if block[d] != _block_req[d]},
                "detail": "sublane/overshoot alignment fit"})
    except YaskException:
        if use_skew and not forced:
            # auto-engaged skew whose wider slabs don't fit the planned
            # pads (small misaligned radii): narrower tilings still fit
            return _fallback("DMA slab rounding exceeds planned pads")
        raise
    _plan_slabs()

    in_tile_bytes, work_bytes = _tile_bytes()
    _block0, _tiles0 = dict(block), in_tile_bytes + work_bytes

    def _shrink_while(too_big) -> bool:
        """Halve the largest shrinkable block dim while
        ``too_big(in_tile_bytes, work_bytes)``; False if it still is
        when nothing can shrink."""
        nonlocal in_tile_bytes, work_bytes
        while too_big(in_tile_bytes, work_bytes):
            shrinkable = [d for d in lead
                          if block[d] > (sub_t if any(
                              _sub_dim(g) == d for g in non_scratch_geoms)
                              else 1)]
            if not shrinkable:
                return False
            d = max(shrinkable, key=lambda dd: block[dd])
            nb = _fit_block(d, max(1, block[d] // 2))
            if nb >= block[d]:
                return False
            block[d] = nb
            _plan_slabs()
            in_tile_bytes, work_bytes = _tile_bytes()
        return True

    # planner-chosen blocks auto-shrink until the tile model fits (its
    # model can undercount misc slots / alignment rounding); explicitly
    # requested blocks fail fast instead — the auto-tuner relies on the
    # raise to mark infeasible candidates
    if not explicit_block:
        _shrink_while(lambda i, w: _over(i + w))
        # plan_blocks grows while two copies of its estimate fit the
        # BUDGET; where the double-buffered tiles do fit it and the
        # class's room is what refuses them, keep the planner's intent
        # (blocks no larger than the input double-buffer allows) rather
        # than keep the blocks and lose the pipelining
        _piped = 2 * in_tile_bytes + work_bytes
        if (pipeline_dmas is not False and _piped <= vmem_budget
                and _over(_piped)):
            _unpiped = dict(block)
            if not _shrink_while(lambda i, w: _over(2 * i + w)):
                block.update(_unpiped)
                _plan_slabs()
                in_tile_bytes, work_bytes = _tile_bytes()
    if block != _block0:
        reasons.append({"code": "block_shrunk", "from": _block0,
                        "to": dict(block),
                        "detail": "tile model over VMEM budget or room"})
        growth_ended = ("budget" if _tiles0 > vmem_budget else "room")
    # Skew feasibility: the skewed dim's carry save-strips must come
    # from the tile's own valid region (block[d] ≥ (D+1)·r, D = deepest
    # carried ring), and the carry buffers must fit the budget
    # alongside the tiles.  Auto-engaged skew falls back to the uniform
    # shrink rather than failing a configuration that still fits.
    if use_skew:
        d_max = max((slots[n] for n in carry_vars), default=0)
        infeasible = any(carry_vars and block[d] < (d_max + 1) * R[d]
                         for d in skew_dims) or \
            _over(in_tile_bytes + work_bytes)
        if infeasible:
            if forced:   # explicitly requested: surface the constraint
                raise YaskException(
                    f"skewed wavefront needs block[d] >= "
                    f"{[(d, (d_max + 1) * R[d]) for d in skew_dims]} "
                    f"(ring {d_max} × radius) and carry within the "
                    f"VMEM budget; got "
                    f"block {[(d, block[d]) for d in skew_dims]}, "
                    f"{(in_tile_bytes + work_bytes)/2**20:.1f} MiB")
            return _fallback("carry floor (ring+1)*r or carry VMEM "
                             "does not fit")

    tile_bytes = in_tile_bytes + work_bytes
    if _over(tile_bytes):
        _refuse("pallas tile needs", tile_bytes,
                "shrink block or fuse_steps")

    # ceil coverage: edge windows overshoot into the (validated) right
    # pads; overshoot cells read zero ghosts and mask to zero writes
    grid = tuple(_gcount(d, block[d]) for d in lead)
    total_steps = int(math.prod(grid)) if grid else 1
    # rows the grid walks past the span, by lead dim (``edge_overhead``
    # is their product's share), and the rows of right pad every
    # allocation holds beyond the tile's own margin, which they lie in
    overshoot = {d: g_ * block[d] - span[d] for g_, d in zip(grid, lead)}
    overshoot_pad = {d: overshoot[d] + min(
        (g.shape[g.axis_of(d)] - _window_end(g, d, block[d])
         for g in window_geoms if d in g.domain_dims), default=0)
        for d in lead}
    for g_, d in zip(grid, lead):
        if span[d] % block[d]:
            reasons.append({
                "code": "block_overshoot", "dim": d, "block": block[d],
                "grid": g_, "overshoot": overshoot[d],
                "pad": overshoot_pad[d]})

    # Double-buffer the input-tile DMAs across grid steps: while step i
    # computes on buffer i%2, step i+1's halo tiles stream into the other
    # buffer (reference prefetch/early-load machinery, Cpp.hpp:263-287).
    # Costs 2x input-tile VMEM; auto-disabled when that busts the budget
    # or there's only one grid step. Grid dims are declared "arbitrary"
    # (sequential) so the linear-index prefetch is sound.
    _pipe_req = pipeline_dmas
    if pipeline_dmas is None:
        pipeline_dmas = (total_steps > 1
                         and not _over(2 * in_tile_bytes + work_bytes))
    use_pipe = bool(pipeline_dmas) and total_steps > 1
    reasons.append(
        {"code": "pipe_in_on",
         "detail": "forced" if _pipe_req else "auto (2*in+work fits)"}
        if use_pipe else
        {"code": "pipe_in_off",
         "detail": ("pipeline_dmas=False requested"
                    if _pipe_req is False
                    else "single grid step" if total_steps <= 1
                    else "2*in+work over VMEM budget or room")})
    if use_pipe:
        tile_bytes = 2 * in_tile_bytes + work_bytes
        if _over(tile_bytes):   # explicitly-requested pipelining
            _refuse("pallas pipelined tiles need", tile_bytes,
                    "shrink block or fuse_steps, or disable "
                    "pipeline_dmas")
    # Pipelined WRITE-back: output DMAs source DEDICATED parity-doubled
    # staging tiles (not the consumed input scratch), so they stay in
    # flight through the whole next grid step's compute — the input
    # prefetch never touches them and each store retires two steps
    # later, just before its parity's staging is re-filled.  Staging
    # through the input scratch cannot overlap anything: the li+1
    # prefetch targets the same parity the li−1 stores source, forcing
    # retirement at the body top with zero instructions since the
    # start.  Costs 2× an output-tile set; auto-disabled when that
    # busts the budget (outputs then stage through the input scratch
    # and drain at the end of each grid step).
    ostage_bytes = 2 * sum(int(math.prod(tile_shape(n))) * esize
                           * min(K, slots[n]) for n in written_out)
    use_pipe_out = use_pipe and not _over(2 * in_tile_bytes + work_bytes
                                          + ostage_bytes)
    if use_pipe_out:
        tile_bytes += ostage_bytes
    scoped_need = _need(tile_bytes)
    live_factor = round(scoped_need / tile_bytes, 3)
    vinstr_est = _vinstr_est()
    reasons.append(
        {"code": "pipe_out_on",
         "detail": "parity-doubled staging fits the budget"}
        if use_pipe_out else
        {"code": "pipe_out_off",
         "detail": ("input pipelining off" if not use_pipe
                    else "staging tiles over VMEM budget or room")})
    if plan_only:
        # The checker's window into the REAL planner: everything above
        # ran (skew ladder, slab rounding, budget shrink, pipelining)
        # but nothing traced or allocated.  Keys are plain
        # JSON-serializable values.
        return {
            "fuse_steps": K,
            "block": dict(block),
            "grid": list(grid),
            "total_steps": total_steps,
            "skew": bool(use_skew),
            "skew_dims": list(skew_dims),
            "push": bool(use_push),
            "push_vars": list(pushed),
            "region": {d: list(region[d]) for d in sorted(restricted)},
            "mL": dict(mL), "mR": dict(mR), "E": dict(E),
            "radius": dict(rad),
            "sizes": dict(sizes),
            "minor": minor,
            "sub_t": sub_t,
            "lane_t": _lane_t,
            "pipeline_dmas": use_pipe,
            "pipeline_out": use_pipe_out,
            "in_tile_bytes": in_tile_bytes,
            "work_bytes": work_bytes,
            "push_tile_bytes": sum(
                slots[n] * int(math.prod(tile_shape(n))) * esize
                for n in pushed),
            "ostage_bytes": ostage_bytes if use_pipe_out else 0,
            "carry_bytes": sum(
                int(math.prod(carry_shape(n))) * esize
                for n in carr_base),
            "tile_bytes": tile_bytes,
            "vmem_budget": vmem_budget,
            "result_bytes": _result_bytes(),
            "scoped_need_bytes": scoped_need,
            "live_factor": live_factor,
            "vinstr_est": vinstr_est,
            "growth_ended": growth_ended,
            "ops_per_point": ops_per_point,
            "dag_ops_per_point": dag_ops_per_point,
            "smem_vars": sorted(smem_vars),
            "dma_vars": list(dma_vars),
            "written": list(written),
            "written_out": list(written_out),
            "scratch_vars": list(scratch_vars),
            "hoisted": list(ana.hoisted),
            "slots": dict(slots),
            "carry_vars": list(carry_vars),
            "tile_shapes": {n: list(tile_shape(n)) for n in var_order},
            "base_off": {f"{n}/{d}": v for (n, d), v in base_off.items()},
            "resid": {f"{n}/{d}": v for (n, d), v in resid.items()},
            "slab": {f"{n}/{d}": v for (n, d), v in slab.items()},
            "overshoot": dict(overshoot),
            "overshoot_pad": dict(overshoot_pad),
            "window_reach": {f"{n}/{d}": v
                             for (n, d), v in window_reach.items()},
            "reasons": list(reasons),
        }
    minor_origin = {n: (g.pads[minor][0]
                        if minor in g.domain_dims else 0)
                    for n, g in program.geoms.items()}
    ev = _TileEval(jnp, program, minor, minor_origin, resid)

    dirn = ana.step_dir

    # global-problem extents for the zero-outside-domain mask; in
    # distributed mode the shard's origin arrives as a traced vector
    gdom = {d: program.global_last[d] + 1 for d in dims}
    nscalars = 2 if distributed else 1  # t0 (+offsets)

    n_inputs = sum(slots[n] for n in var_order) + nscalars
    # ``onto``: the caller's arrays ride behind the inputs, aliased to
    # the outputs one to one and read by nothing
    n_base = (sum(min(K, slots[n]) for n in written_out) if onto else 0)

    in_base: Dict[str, int] = {}   # var -> first input-ref index
    _ii = 0
    for _n in var_order:
        in_base[_n] = _ii
        _ii += slots[_n]
    si_base: Dict[str, int] = {}   # DMA var -> first scratch-tile index
    _si = 0
    for _n in dma_vars:
        si_base[_n] = _si
        _si += slots[_n]

    # ---- strip plan -------------------------------------------------------
    # The strip evaluator keeps every tile in its VMEM buffer and walks
    # a stage's region in strips of a few vector registers.  What each
    # equation of each sub-step reads, and the buffer its strips are
    # stored to, is static: planned here, executed by the kernel.
    # Buffers are named by key: ("in", var, j) the j-th ring slot's
    # input tile, ("res", var) a var's explicit result tile, ("scr",
    # var) a scratch var's tile.
    # the written vars that keep, where their conditions leave points
    # of the domain out, what the slot they are written into held: that
    # slot is read, and seeds what is stored
    kept = ana.kept_vars(lambda conds: conds_cover_domain(program, conds))

    def _plan_strips():
        """``(sub-steps, final rings)``: per fused sub-step the rings
        at its top and its walks in order -- a walk is one region and
        the equations evaluated strip by strip over it, each with its
        destination buffer and the rings and results its reads resolve
        against -- or ``(None, why)`` where a strip would read cells
        an earlier strip of the same walk has already overwritten."""
        in_place = _in_place
        points = {id(eq): _eq_points(eq) for eq in ana.eqs}
        cost = {id(eq): _eq_cost(eq, ana.sincos_args) for eq in ana.eqs}
        rd = _TileEval(jnp, program, minor, minor_origin, resid)
        rd.scratch = {n: ("scr", n) for n in scratch_vars}
        rings = {n: [("in", n, j) for j in range(slots[n])]
                 for n in var_order}
        free = {n: ("res", n) for n in written if not in_place[n]}

        def offset_read(ep, key):
            """Does ``ep`` read buffer ``key`` away from the point it
            writes?"""
            return any(k == key and any(pt.domain_offsets().values())
                       for k, pt in ep["reads"])

        subs = []
        for k in range(K):
            computed: Dict[str, tuple] = {}
            seeded = set()
            sub = {"rings": dict(rings), "walks": []}
            for si in range(nstages):
                region = stage_region(k, si)
                walk = None
                for part in ana.stages[si].parts:
                    part_misc = has_misc_value and any(
                        uses_misc_index(eq.rhs, eq.cond, eq.step_cond)
                        for eq in part.eqs)
                    for eq in part.eqs:
                        name = eq.lhs.var_name()
                        g = program.geoms[name]
                        ep = {"eq": eq, "name": name,
                              "ops": cost[id(eq)],
                              "scratch": part.is_scratch,
                              "own_memo": part.is_scratch or part_misc,
                              "rings": dict(rings),
                              "computed": dict(computed),
                              "seed": None, "zero_base": False,
                              "reads": [(rd.source(pt, rings, computed),
                                         pt) for pt in points[id(eq)]]}
                        if part.is_scratch:
                            wreg = scratch_region(name, region)
                            ep["dest"] = ("scr", name)
                            ep["zero_base"] = name not in seeded
                            seeded.add(name)
                        else:
                            wreg = list(region)
                            # the var's first equation of the sub-step
                            # starts from what the evicted slot held
                            # only where the var's conditions leave
                            # points of the domain out
                            # (``analysis.kept_vars``): outside the
                            # domain every slot is zero
                            # (a plane at a time: one a binding of
                            # the left-hand side's misc indices)
                            plane = (name, tuple(sorted(
                                eq.lhs.misc_vals().items())))
                            ep["zero_base"] = (name not in kept
                                               and plane not in seeded)
                            seeded.add(plane)
                            if name in computed:
                                ep["dest"] = computed[name]
                            elif in_place[name]:
                                ep["dest"] = rings[name][0]
                            else:
                                ep["dest"] = free[name]
                                if name in kept:
                                    ep["seed"] = rings[name][0]
                            computed[name] = ep["dest"]
                        # a var that lacks a lead dim is constant along
                        # it (analysis race rule): its equation walks the
                        # one row at global coordinate pid*block, in the
                        # domain for every tile
                        wreg = [(mL[d], mL[d] + 1)
                                if d != minor and d not in g.domain_dims
                                else r for d, r in zip(dims, wreg)]
                        if offset_read(ep, ep["dest"]):
                            return None, (
                                f"'{name}' is read at an offset from "
                                "the buffer its strips are stored to")
                        # equations share a walk (its loop, its loads
                        # and, across a stage's final equations, its
                        # memo) where they cover one region and none
                        # needs what another's strips store: a stage's
                        # final parts do not depend on each other, nor
                        # do the equations of one scratch part
                        if (walk is not None
                                and walk["scratch"] == part.is_scratch
                                and (walk["part"] is part
                                     or not part.is_scratch)
                                and walk["region"] == wreg
                                and not any(
                                    offset_read(ep, o["dest"])
                                    or offset_read(o, ep["dest"])
                                    for o in walk["eqs"])):
                            walk["eqs"].append(ep)
                        else:
                            walk = {"region": wreg, "eqs": [ep],
                                    "scratch": part.is_scratch,
                                    "part": part}
                            sub["walks"].append(walk)
            for name in written:
                evicted = rings[name][0]
                rings[name] = rings[name][1:] + [computed[name]] \
                    if slots[name] >= 2 else [computed[name]]
                if not in_place[name]:
                    free[name] = evicted
            subs.append(sub)
        return subs, rings

    # The push arm, which has never met Mosaic, keeps the whole-tile
    # evaluator, as does a solution with no lead dim to walk (one
    # full-lane tile, empty grid).
    strip_subs = strip_rings = None
    if _tile_eval:
        eval_why = "whole-tile evaluator requested (_tile_eval)"
    elif use_push:
        eval_why = "push arm"
    elif not lead:
        eval_why = "no lead dim to walk"
    else:
        strip_subs, strip_rings = _plan_strips()
        eval_why = strip_rings if strip_subs is None else ""
    use_strip = strip_subs is not None
    reasons.append({"code": "eval_strip"} if use_strip else
                   {"code": "eval_tile", "detail": eval_why})
    # vars with an explicit result tile, and the scratch vars' tiles
    res_vars = sorted({ep["dest"][1] for sub in strip_subs or []
                       for walk in sub["walks"] for ep in walk["eqs"]
                       if ep["dest"][0] == "res"})
    scr_vars = list(scratch_vars) if use_strip else []

    # The strip's shape, from what the build observes.  A strip is ONE
    # value: lead rows x sublane rows x the whole minor extent.  The
    # chip (PR 44's A/B, ``PERF.md`` section 6) wants it large: every
    # strip pays a fixed ~175 cycles beside its schedule, whatever its
    # size (flagship: sublane groups of 8 rows 31.5 ms a step, of 16
    # 25.5, of 32 23.3; then 2 lead rows 20.7, 4 20.4, 8 20.5), until
    # its value outgrows what the scheduler hides in spills, at 50-100
    # registers.  So: the lanes of the widest minor extent a walk
    # evaluates (a scratch var's grown by its write halo) and the
    # register tiles of the region's sublane extent fix the registers
    # of one lead row; the strip takes the whole sublane extent (in
    # equal parts where one row of it is over ``_STRIP_VREGS``) and as
    # many lead rows, a power of two, as keep it within
    # ``_STRIP_VREGS``; at least ``_STRIP_ROWS`` where an equation
    # reads a shifted window at several lead rows (a diagonal read:
    # cube's 27 points), whose cover window is loaded and shifted once
    # for the rows of a strip.
    strip_shape = (0, 0)
    strips = strip_vregs = 0
    if use_strip:
        walks_ = [w for sub in strip_subs for w in sub["walks"]]
        lanes = max(-(-(w["region"][-1][1] - w["region"][-1][0])
                      // _lane_t) for w in walks_)
        sub_ext = max(w["region"][-2][1] - w["region"][-2][0]
                      for w in walks_)
        row_span = 1
        if len(lead) > 1:
            shifted: Dict[tuple, set] = {}
            for w in walks_:
                for ep in w["eqs"]:
                    for key, pt in ep["reads"]:
                        offs = pt.domain_offsets()
                        side = tuple(offs.get(d, 0) for d in dims[-2:])
                        if any(side):
                            shifted.setdefault(
                                (id(ep), key, side,
                                 tuple(sorted(pt.misc_vals().items()))),
                                set()).add(offs.get(lead[-2], 0))
            row_span = max((max(o) - min(o) + 1
                            for o in shifted.values()), default=1)
        tall = -(-sub_ext // sub_t)
        fit = max(1, _STRIP_VREGS // lanes)
        if _strip is not None:
            strip_shape = (int(_strip[0]), int(_strip[1]))
        elif tall > fit:
            # the sublane extent in strips of equal height
            strip_shape = (1, sub_t * -(-tall // -(-tall // fit)))
        else:
            sx = 1
            if len(lead) > 1:
                sx = 1 << (max(1, _STRIP_VREGS // (lanes * tall))
                           .bit_length() - 1)
                if row_span > 1:
                    sx = max(sx, _STRIP_ROWS)
            strip_shape = (sx, sub_t * tall)
        sx_, sy_ = strip_shape
        for w in walks_:
            ext = [hi - lo for lo, hi in w["region"]]
            rows = math.prod(ext[:-3]) * -(-ext[-3] // sx_) \
                if len(ext) > 2 else 1
            strips += rows * -(-ext[-2] // sy_)
        row_ext = max(w["region"][-3][1] - w["region"][-3][0]
                      for w in walks_) if len(dims) > 2 else 1
        strip_vregs = (min(sx_, row_ext)
                       * -(-min(sy_, sub_ext) // sub_t) * lanes)

    # ---- fetch windows ----------------------------------------------------
    # The input DMA of a (var, slot) copies the window the stage chain
    # reads of it, not the step's whole slab: the reach of a fused step
    # belongs to the chain, not to each array.  A read of stage ``si``
    # at sub-step ``k`` that still falls on a slot the launch fetched
    # (a written var's ring has turned ``k`` times by then: beyond its
    # last slot lies a level the kernel computed itself) needs
    # ``stage_region(k, si)`` grown by the read's widths; a slot's
    # window is the hull of those in each lead dim, clipped to the
    # slab, on the var's sublane axis rounded out to the tile like the
    # slab itself.  Buffers, tile shapes, ``resid``, regions and read
    # indices stay what they are: the copy lands in the same window of
    # the same buffer, and the rows outside it hold whatever was there
    # -- by construction no stage reads them.  A slot with no such read
    # has no window and no DMA (its buffer stays: a strip is stored
    # into it).  Whole slabs stay where ``stage_region`` alone cannot
    # argue the window: under the whole-tile evaluator (it loads every
    # tile as one value and stores the produced slots whole), and in a
    # skewed dim (the carry patches strips left of the slid regions).
    # In buffer rows (``resid`` included), by lead dim.
    fetch_win: Dict[Tuple[str, int], Dict[str, Tuple[int, int]]] = {}
    if use_strip:
        from yask_tpu.compiler.analysis import EVICTED
        _hull: Dict[Tuple[str, int], Dict[str, Tuple[int, int]]] = {}
        _reads = ana.stage_ring_reads(kept)
        for k in range(K):
            for si in range(nstages):
                _reg = dict(zip(lead, stage_region(k, si)))
                for _n, _so, _widths in _reads[si]:
                    if _n not in dma_vars:
                        continue    # an SMEM rider
                    g = program.geoms[_n]
                    j = (0 if _so == EVICTED
                         else slots[_n] - 1 if _so is None
                         or not g.is_written
                         else slots[_n] - 1 + _so * ana.step_dir)
                    if g.is_written:
                        j += k
                    if j >= slots[_n]:
                        continue    # a level this kernel computed
                    _win = _hull.setdefault((_n, j), {})
                    for d in lead:
                        if d not in g.domain_dims:
                            continue
                        wl, wr = _widths.get(d, (0, 0))
                        lo, hi = _win.get(d, (_reg[d][1], _reg[d][0]))
                        _win[d] = (min(lo, _reg[d][0] - wl),
                                   max(hi, _reg[d][1] + wr))
        for (_n, j), _win in _hull.items():
            g = program.geoms[_n]
            fetch_win[_n, j] = {}
            for d, (lo, hi) in _win.items():
                lo, hi = lo + resid[_n, d], hi + resid[_n, d]
                if d in skew_set:
                    lo, hi = 0, slab[_n, d]
                elif _sub_dim(g) == d:
                    lo, hi = (lo // sub_t) * sub_t, -(-hi // sub_t) * sub_t
                fetch_win[_n, j][d] = (max(lo, 0), min(hi, slab[_n, d]))
        for d in skew_dims:
            reasons.append({"code": "fetch_whole", "dim": d,
                            "detail": "skewed dim: the carry patches "
                                      "strips left of the slid regions"})
    else:
        for _n in dma_vars:
            for j in range(slots[_n]):
                fetch_win[_n, j] = {
                    d: (0, slab[_n, d])
                    for d in program.geoms[_n].domain_dims if d != minor}
        reasons.append({"code": "fetch_whole", "detail": eval_why})
    fetch_skipped = [(n, j) for n in dma_vars for j in range(slots[n])
                     if (n, j) not in fetch_win]
    # ``reuse_evicted``: output -> the evicted slot it is written onto
    # (the launch rotates the oldest min(K, slots) slots out)
    reused: Dict[int, Tuple[str, int]] = {}
    if reuse_evicted and not onto:
        reused = {oi: slot for oi, slot in enumerate(
            (n, j) for n in written_out for j in range(min(K, slots[n])))
            if slot in fetch_skipped}

    def _window_points(name, rows):
        """Points of var ``name``'s tile with ``rows(d)`` rows in lead
        dim ``d``: the minor dim and misc axes ride whole."""
        return int(math.prod(
            ext if kind == "misc" or dn == minor else rows(dn)
            for ext, (dn, kind) in zip(tile_shape(name),
                                       program.geoms[name].axes)))

    def out_rows(name, lvl, dn):
        """``(left shift, rows)`` of the output copy of var ``name``'s
        time level ``lvl`` in lead dim ``dn``: the block's own rows
        but in a skewed dim, where level lvl's write region sits
        shifted left by (lvl−1)·r.  On the var's sublane axis,
        sublane-multiple shifts express exactly; others round the shift
        DOWN to the sublane tile and widen the window by one tile: both
        ends stay inside the level's valid span (E_sk budgeted it), and
        the sub_t overlap with the next sequential tile re-writes
        identical valid values (src and dst starts share the same
        residue, g.origin ≡ mL+resid (mod 8)).  Where it is not the
        var's sublane axis it is an untiled DMA axis: the shift
        expresses exactly."""
        if dn not in skew_set:
            return 0, block[dn]
        shift = (lvl - 1) * R[dn]
        if _sub_dim(program.geoms[name]) != dn:
            return shift, block[dn]
        sh_al = (shift // sub_t) * sub_t
        return sh_al, block[dn] + (sub_t if sh_al != shift else 0)

    def kernel(*refs):
        # refs: t0 (SMEM), [offsets (SMEM)], inputs (ANY/HBM) ...,
        #       outputs (ANY/HBM, padded shapes) ..., scratch tiles ...,
        #       input-DMA sem, output-DMA sem
        t0_ref = refs[0]
        off_ref = refs[1] if distributed else None
        ins = refs[nscalars:n_inputs]
        nout = sum(min(K, slots[n]) for n in written_out)
        _ob = n_inputs + n_base
        outs = refs[_ob:_ob + nout]
        n_tiles = sum(slots[n] for n in dma_vars)
        scratch = refs[_ob + nout:_ob + nout + n_tiles]
        _cb = _ob + nout + n_tiles
        carr = refs[_cb:_cb + len(carr_base)]
        _xb = len(refs) - 2 - len(res_vars) - len(scr_vars)
        ostage = refs[_cb + len(carr_base):_xb]
        res_refs = dict(zip(res_vars, refs[_xb:]))
        scr_refs = dict(zip(scr_vars, refs[_xb + len(res_vars):]))
        sem = refs[-2]
        out_sem = refs[-1]

        pid = [pl.program_id(i) for i in range(len(lead))]

        def _coords(step):
            """Decode a linear sequential-grid index into per-dim
            coordinates (shared by the prefetch / retire / drain
            paths)."""
            cs = []
            rem_ = step
            for i in range(len(lead) - 1, -1, -1):
                cs.append(rem_ % grid[i])
                rem_ = rem_ // grid[i]
            return cs[::-1]

        def out_copies(coords):
            """``(output index, var, slot, source window, destination
            window)`` of every output copy for grid position
            ``coords``."""
            oi = 0
            for name in written_out:
                g = program.geoms[name]
                nback = min(K, slots[name])
                for s in range(nback):
                    lvl = K - nback + s + 1   # time level this slot holds
                    src_idxs = []
                    dst_idxs = []
                    for dn, kind in g.axes:
                        if kind == "misc" or dn == minor:
                            src_idxs.append(slice(None))
                            dst_idxs.append(slice(None))
                        elif dn in skew_set:
                            sh_al, wsz = out_rows(name, lvl, dn)
                            src_idxs.append(pl.ds(
                                mL[dn] - sh_al + resid[name, dn], wsz))
                            dst_idxs.append(pl.ds(
                                g.origin[dn] - sh_al
                                + coords[lead.index(dn)] * block[dn],
                                wsz))
                        else:
                            di = lead.index(dn)
                            src_idxs.append(pl.ds(
                                mL[dn] + resid[name, dn], block[dn]))
                            dst_idxs.append(pl.ds(
                                g.origin[dn] + reg_lo[dn]
                                + coords[di] * block[dn],
                                block[dn]))
                    yield oi, name, s, tuple(src_idxs), tuple(dst_idxs)
                    oi += 1

        def out_dmas(coords, par):
            """The full set of output copies for grid position ``coords``
            and staging parity ``par`` — reconstructed identically to
            start and to wait (the wait may happen one grid step later,
            see the pipelined retirement below).  Their source: the
            parity's staging tile where the write-back is pipelined,
            else the buffer that holds the produced slot -- under the
            strip evaluator where the ring left it, under the
            whole-tile one the var's consumed input tile ``s``."""
            cps = []
            for oi, name, s, src_idxs, dst_idxs in out_copies(coords):
                if use_pipe_out:
                    sref = ostage[oi].at[par]
                    osem = out_sem.at[par, oi]
                else:
                    osem = out_sem.at[oi]
                    key = ("in", name, s)
                    if use_strip:
                        ring = strip_rings[name]
                        key = ring[len(ring) - min(K, slots[name]) + s]
                    if key[0] == "res":
                        sref = res_refs[name]
                    else:
                        sref = scratch[si_base[name] + key[2]]
                        if use_pipe:
                            sref = sref.at[par]
                cps.append(pltpu.make_async_copy(
                    sref.at[src_idxs], outs[oi].at[dst_idxs], osem))
            return cps

        # 1) DMA halo tiles HBM → VMEM (double-buffered across grid
        #    steps when use_pipe: compute on buffer li%2 while the next
        #    step's tiles stream into the other buffer).
        def in_dmas(coords, buf):
            """The full set of input-tile copies for grid position
            ``coords`` into buffer ``buf`` (reconstructed identically to
            start and to wait)."""
            out = []
            for n in dma_vars:
                g = program.geoms[n]
                for s in range(slots[n]):
                    win = fetch_win.get((n, s))
                    if win is None:
                        continue    # no stage reads this slot
                    si = si_base[n] + s
                    src = ins[in_base[n] + s]
                    idxs = []
                    widxs = []
                    for dn, kind in g.axes:
                        if kind == "misc" or dn == minor:
                            idxs.append(slice(None))  # full (lane) extent
                            widxs.append(slice(None))
                        else:
                            di = lead.index(dn)
                            # sublane-aligned window; the sub-tile
                            # residual is a static shift the kernel
                            # applies at read/write time
                            lo, hi = win[dn]
                            start = (coords[di] * block[dn]
                                     + base_off[n, dn])
                            idxs.append(pl.ds(start + lo, hi - lo))
                            widxs.append(pl.ds(lo, hi - lo))
                    if use_pipe:
                        dst = scratch[si].at[buf]
                        s_at = sem.at[buf, si]
                    else:
                        dst = scratch[si]
                        s_at = sem.at[si]
                    if any(win[d] != (0, slab[n, d]) for d in win):
                        # the same window of the same buffer
                        dst = dst.at[tuple(widxs)]
                    out.append(pltpu.make_async_copy(
                        src.at[tuple(idxs)] if idxs else src, dst, s_at))
            return out

        if use_pipe:
            li = pid[0]
            for i in range(1, len(lead)):
                li = li * grid[i] + pid[i]
            cur = li % 2

            @pl.when(li == 0)
            def _warmup():
                for dma in in_dmas(pid, 0):
                    dma.start()

            nxt = li + 1
            nxt_coords = _coords(nxt)

            if use_pipe_out:
                # Retire the li−2 output DMAs (same staging parity as
                # this step, cur) before this step's staging re-fills
                # it.  Those copies got a full grid step (li−1's
                # compute) of flight time, so this wait is ~free —
                # the store path no longer serializes the grid.
                pp_coords = _coords(li - 2)

                @pl.when(li >= 2)
                def _retire_out():
                    for cp in out_dmas(pp_coords, cur):
                        cp.wait()

            @pl.when(nxt < total_steps)
            def _prefetch():
                for dma in in_dmas(nxt_coords, nxt % 2):
                    dma.start()

            for dma in in_dmas(pid, cur):
                dma.wait()
        else:
            cur = None
            for dma in in_dmas(pid, None):
                dma.start()
            for dma in in_dmas(pid, None):
                dma.wait()

        def buf_ref(si):
            return scratch[si].at[cur] if use_pipe else scratch[si]

        def copy_box(dst, src, box_dst, box_src, keep=None):
            """Copy a static box ref to ref, ``(start, size)`` an axis:
            a loop over the leading axes, ``_COPY_ROWS`` rows of the
            innermost of them an iteration (one value: the last two
            axes' window of those rows).  ``keep`` (a traced scalar)
            zeroes what is copied where it is false."""
            nrow = max(len(box_dst) - 2, 0)
            outer = [sz for _st, sz in box_dst[:max(nrow - 1, 0)]]
            inner = box_dst[nrow - 1][1] if nrow else 1
            chunk = min(_COPY_ROWS, inner)

            def piece(ii, i, rows):
                """``rows`` rows from ``i`` of the innermost leading
                axis, at ``ii`` in the outer ones."""
                def idx(box):
                    lead_ix = [pl.ds(st + o, 1)
                               for (st, _sz), o in zip(box, ii)]
                    if nrow:
                        lead_ix.append(pl.ds(box[nrow - 1][0] + i, rows))
                    return tuple(lead_ix) + tuple(
                        slice(st, st + sz) for st, sz in box[nrow:])
                val = src[idx(box_src)]
                if keep is not None:
                    val = jnp.where(keep, val, jnp.zeros_like(val))
                dst[idx(box_dst)] = val

            def inner_rows(ii):
                full = inner // chunk
                if full > 1:
                    pl.loop(0, full)(lambda i: piece(ii, i * chunk, chunk))
                elif full == 1:
                    piece(ii, 0, chunk)
                if inner % chunk:
                    piece(ii, full * chunk, inner % chunk)

            def outer_body(i):
                ii = []
                for e in outer[:0:-1]:
                    ii.append(i % e)
                    i = i // e
                inner_rows([i] + ii[::-1])

            if math.prod(outer) == 1:
                inner_rows([0] * len(outer))
            else:
                pl.loop(0, math.prod(outer))(outer_body)

        def whole(name):
            return [(0, e) for e in tile_shape(name)]

        def strip_body():
            """The strip evaluator: tiles stay in their VMEM buffers;
            each walk of the plan loops over the rows of the untiled
            lead dims (a dynamic index into the refs) with the sublane
            groups unrolled inside, evaluates its equations on one
            strip at a time and stores the strip where it belongs."""
            sev = _StripEval(jnp, program, minor, minor_origin, resid,
                             pl=pl, pltpu=pltpu, sub_t=sub_t)
            sev.gidx_base = {
                d: pid[lead.index(d)] * block[d] + _goff(d) for d in lead}
            if distributed:
                for di, d in enumerate(dims):
                    sev.gidx_base[d] = sev.gidx_base.get(d, 0) + off_ref[di]
            # every buffer's ref, made here: one made inside a loop's
            # body (the parity view indexes by a traced value) must not
            # outlive that body's trace
            bufs: Dict[tuple, _Buf] = {}
            for n in var_order:
                for j in range(slots[n]):
                    bufs["in", n, j] = _Buf(
                        ("in", n, j),
                        ins[in_base[n] + j] if n in smem_vars
                        else buf_ref(si_base[n] + j))
            for n in res_vars:
                bufs["res", n] = _Buf(("res", n), res_refs[n])
            for n in scr_vars:
                bufs["scr", n] = _Buf(("scr", n), scr_refs[n])
            buf = bufs.__getitem__
            sev.scratch = {n: buf(("scr", n)) for n in scr_vars}
            # the buffers no DMA fills: a result tile, a slot with no
            # window.  The strips stored into them bring the minor
            # pads' zeros along (``_StripEval.store``)
            unfilled = {("res", n) for n in res_vars} | {
                ("in", n, j) for n, j in fetch_skipped}
            sx, sy = strip_shape

            def eval_strip(walk):
                """Every equation of the walk on the strip ``sev.strip``."""
                sshape = sev.shape()
                mask = None
                if not walk["scratch"]:
                    # global-domain mask over the strip's lead dims (the
                    # GLOBAL problem's bounds in distributed mode)
                    for di, d in enumerate(lead):
                        sbase, off, size = sev.strip[di]
                        shape = [1] * len(dims)
                        shape[di] = size
                        gidx = (lax.broadcasted_iota(
                                    jnp.int32, tuple(shape), di)
                                + off + pid[di] * block[d] + _goff(d))
                        if sbase is not None:
                            gidx = gidx + sbase
                        if distributed:
                            gidx = gidx + off_ref[di]
                            bound = gdom[d]
                        else:
                            bound = sizes[d]
                        m = (gidx >= 0) & (gidx < bound)
                        mask = m if mask is None else mask & m
                memo: Dict = {}
                for ep in walk["eqs"]:
                    eq, name = ep["eq"], ep["name"]
                    if ep["own_memo"]:
                        memo = {}
                    lmisc = sev.misc_env = eq.lhs.misc_vals()
                    if "tiles" not in ep:   # once an equation, not a strip
                        ep["tiles"] = {n: [buf(key) for key in ring]
                                       for n, ring in ep["rings"].items()}
                        ep["results"] = {n: buf(key) for n, key
                                         in ep["computed"].items()}
                    tiles, computed = ep["tiles"], ep["results"]
                    val = sev.eval(eq.rhs, tiles, computed, memo)
                    val = jnp.broadcast_to(
                        jnp.asarray(val, dtype=dtype), sshape)
                    sel = mask
                    if eq.cond is not None:
                        cm = sev.eval(eq.cond, tiles, computed, memo)
                        sel = cm if sel is None else sel & cm
                    if eq.step_cond is not None and not ep["scratch"]:
                        sc = sev.eval(eq.step_cond, tiles, computed, memo)
                        sel = sc if sel is None else sel & sc
                    g = program.geoms[name]
                    if g.domain_dims != dims:
                        # the strip is one row wide in the dims the var
                        # lacks: collapse to its own axes
                        vshape = tuple(sz for d, sz in zip(dims, sshape)
                                       if d in g.domain_dims)
                        val = val.reshape(vshape)
                        if sel is not None:
                            sel = jnp.broadcast_to(
                                sel, sshape).reshape(vshape)
                    dest = buf(ep["dest"])
                    if sel is not None:
                        # unselected points keep the base (evicted-slot
                        # / earlier-write) values; zeros under a scratch
                        # var's first equation, and under a var's
                        # unconditional first one (what it leaves out lies
                        # outside the domain, where every slot is zero:
                        # the evicted slot need not have been fetched)
                        base = (jnp.zeros(val.shape, dtype)
                                if ep["zero_base"]
                                else sev.load(dest, name, None, lmisc))
                        val = jnp.where(sel, val, base)
                    sev.store(dest, name, val, lmisc,
                              whole_lanes=ep["dest"] in unfilled)

            def realign_pays(walk, y0, ysz):
                """Sublane rows that start off the register tile make
                every value of the strip a register taller than its
                rows need (24 rows from row 4 lie in four tiles, not
                three).  Evaluating them rotated onto the tile saves
                that register on every operation and costs a rotate
                and a select a register of every window that does not
                land on the tile, the result's among them; as they
                stand, only the windows at a sublane offset are
                shifted.  Reckoned in register operations a point
                (``_eq_cost``), a window at a lane offset at 4 more (its
                lane rotates are done on the strip's registers, a
                register taller or not)."""
                name0 = walk["eqs"][0]["name"]
                start = (y0 + resid.get((name0, lead[-1]), 0)) % sub_t
                tall = -(-ysz // sub_t)
                tall_off = -(-(start + ysz) // sub_t)
                if tall_off == tall:
                    return False
                ops = sum(ep["ops"] for ep in walk["eqs"])
                shifts = {(key, pt.skey()): (
                              pt.domain_offsets().get(lead[-1], 0),
                              pt.domain_offsets().get(minor, 0))
                          for ep in walk["eqs"] for key, pt in ep["reads"]
                          if lead[-1] in program.geoms[
                              pt.var_name()].domain_dims}
                as_is = sum(1 for o, _z in shifts.values() if o % sub_t)
                rotated = len(walk["eqs"]) + sum(
                    1 for o, _z in shifts.values() if (start + o) % sub_t)
                # a lane rotate is about two bundles where a sublane
                # rotate and its select are one each
                ops += 4 * sum(1 for _o, z in shifts.values() if z)
                return (ops * tall + 2 * rotated * tall_off
                        < ops * tall_off + 2 * as_is * tall_off)

            def run_walk(walk):
                region = walk["region"]
                for ep in walk["eqs"]:
                    if ep["seed"] is not None:
                        copy_box(buf(ep["dest"]).ref, buf(ep["seed"]).ref,
                                 whole(ep["name"]), whole(ep["name"]))
                # the lead rows each window is read at, so that a
                # window read at several is loaded (and shifted into
                # place) once for all of them
                sev.row_cover = {}
                if len(lead) > 1:
                    for ep in walk["eqs"]:
                        for key, pt in ep["reads"]:
                            offs = dict(pt.domain_offsets())
                            o = offs.pop(lead[-2], 0)
                            ck = sev.cover_key(key, offs, pt.misc_vals())
                            lo_, hi_ = sev.row_cover.get(ck, (o, o))
                            sev.row_cover[ck] = (min(lo_, o), max(hi_, o))
                lo_s, hi_s = region[len(lead) - 1]
                groups = [(y0, min(sy, hi_s - y0))
                          for y0 in range(lo_s, hi_s, sy)]
                m_lo, m_hi = region[-1]

                def rows_body(rows, nrows):
                    """``nrows`` rows of the innermost row dim from
                    ``rows[-1]``, one sublane group at a time: each a
                    strip, evaluated as one value."""
                    for y0, ysz in groups:
                        sev.loads = {}
                        sev.realign = realign_pays(walk, y0, ysz)
                        sev.strip = [
                            (b, o, nrows if i == len(rows) - 1 else 1)
                            for i, (b, o) in enumerate(rows)] + [
                            (None, y0, ysz), (None, m_lo, m_hi - m_lo)]
                        eval_strip(walk)

                def loop(di, rows):
                    lo, hi = region[di]
                    if di < len(lead) - 2:
                        # an outer row dim: one row an iteration
                        pl.loop(0, hi - lo)(
                            lambda i: loop(di + 1, rows + [(i, lo)]))
                        return
                    full = (hi - lo) // sx
                    if full > 1:
                        pl.loop(0, full)(
                            lambda i: rows_body(rows + [(i * sx, lo)], sx))
                    elif full == 1:
                        rows_body(rows + [(None, lo)], sx)
                    if (hi - lo) % sx:
                        rows_body(rows + [(None, lo + full * sx)],
                                  (hi - lo) % sx)

                if len(lead) == 1:
                    rows_body([], 1)
                else:
                    loop(0, [])

            def strip_box(name, lo, width, in_tile=True):
                """Rows ``lo .. lo + width`` of the stream dim, every
                other axis whole: in a tile (from the var's static
                shift) or in its carry buffer (from 0)."""
                rs = resid.get((name, sdim), 0) if in_tile else 0
                return [(rs + lo, width)
                        if kind == "domain" and dn == sdim else (0, e)
                        for e, (dn, kind) in zip(
                            tile_shape(name), program.geoms[name].axes)]

            def carry_ref(name, lvl):
                return carr[carr_base[name]].at[lvl - 1]

            for k, sub in enumerate(strip_subs):
                sev.t = t0_ref[0] + k * dirn
                # the carry patches and the delayed saves of the
                # whole-tile evaluator (see the helpers there), ref to
                # ref: the neighbouring tile's strips into the live ring
                # levels' buffers, then this tile's into the carry
                if carr_base and k >= 1:
                    r_sk = R[sdim]
                    for n in carr_base:
                        Dn = slots[n]
                        ring = sub["rings"][n]
                        for j in range(len(ring)):
                            lvl = k - (len(ring) - 1 - j)
                            if lvl < 1:
                                continue
                            width = (2 if lvl == k else 1) * r_sk
                            # dim start: the left margin is
                            # out-of-domain ghost: zero
                            copy_box(
                                buf(ring[j]).ref, carry_ref(n, lvl),
                                strip_box(n, (K - k - 1) * r_sk, width),
                                strip_box(n, (lvl + Dn - k - 1) * r_sk,
                                          width, in_tile=False),
                                keep=pid[-1] > 0)
                    for n in carr_base:
                        Dn = slots[n]
                        ring = sub["rings"][n]
                        if k < K - 1:
                            lvls = ([k - Dn + 1] if k - Dn + 1 >= 1
                                    else [])
                        else:
                            lvls = list(range(max(1, K - Dn), K))
                        for lvl in lvls:
                            width = (Dn + 1) * r_sk
                            copy_box(
                                carry_ref(n, lvl),
                                buf(ring[Dn - 1 - (k - lvl)]).ref,
                                strip_box(n, 0, width, in_tile=False),
                                strip_box(n, block[sdim]
                                          + (K - lvl - Dn) * r_sk, width))
                for walk in sub["walks"]:
                    run_walk(walk)

            # the produced slots are where the ring left them: the
            # output DMAs read those buffers, or, where the write-back
            # is pipelined, the windows they read are copied to the
            # parity's staging tile
            if use_pipe_out:
                for oi, name, s, src_idxs, _dst in out_copies(pid):
                    ring = strip_rings[name]
                    box = [(0, e) if isinstance(ix, slice)
                           else (ix.start, ix.size)
                           for ix, e in zip(src_idxs, tile_shape(name))]
                    copy_box(ostage[oi].at[cur],
                             buf(ring[len(ring) - min(K, slots[name])
                                      + s]).ref, box, box)

        def tile_body():
            """The whole-tile evaluator: every DMA'd tile loaded as ONE
            value, every stage's result a whole-tile value (the evicted
            base with the region written over it by pad + iota masks +
            select), the produced slots stored whole.  The push arm,
            which has never met Mosaic, keeps it, and the tests hold
            the strip evaluator to it bit for bit."""
            # tiles as values; SMEM vars stay as refs (scalar static reads).
            # Pushed vars were never DMA'd: their ring seeds are ZERO tiles
            # — bit-equivalent to the HBM state on every cell a consumer
            # can reach (out-of-domain cells are ghost-zero in HBM too, and
            # every read is a same-sub-step ``computed`` read that never
            # touches these seeds).
            tiles: Dict[str, List] = {}
            for n in var_order:
                if n in smem_vars:
                    tiles[n] = [ins[in_base[n] + s] for s in range(slots[n])]
                elif n in pushed_set:
                    tiles[n] = [jnp.zeros(tile_shape(n), dtype)
                                for _ in range(slots[n])]
                else:
                    tiles[n] = [buf_ref(si_base[n] + s)[...]
                                for s in range(slots[n])]

            # 2) K fused sub-steps; within each, every stage consumes its read
            #    radius of tile margin (the uniform shrink) and writes a FULL
            #    tile (base.at[region].set) so later stages read it at offsets.
            def region_idxs(name, region, misc=None):
                """Index tuple over the var's own axes: domain axes sliced to
                the region (minor shifted by the var's pad origin), misc axes
                pinned to the LHS misc values (ints — they collapse, so the
                result of base[idxs] is region-shaped)."""
                g = program.geoms[name]
                idxs = []
                for dn, kind in g.axes:
                    if kind == "misc":
                        idxs.append((misc or {})[dn] - g.misc_lo[dn])
                    elif dn == minor:
                        mo = g.pads[minor][0]
                        idxs.append(slice(mo + region[-1][0],
                                          mo + region[-1][1]))
                    else:
                        lo, hi = region[dims.index(dn)]
                        rs = resid.get((name, dn), 0)
                        idxs.append(slice(rs + lo, rs + hi))
                return tuple(idxs)

            def to_var_region(name, val, region):
                """Slice a full-region value down to a partial-dim var's own
                axes.  The RHS is constant along the missing lead dims
                (XLA-path `_to_var_layout` contract), so the cell at global
                coordinate pid·block — in-domain for every tile by the ceil
                grid construction — is taken."""
                g = program.geoms[name]
                if g.domain_dims == dims:
                    return val
                idx = []
                for di, d in enumerate(dims):
                    if d in g.domain_dims:
                        idx.append(slice(None))
                    else:
                        lo, _hi = region[di]
                        idx.append(mL[d] - lo)
                return val[tuple(idx)]

            def tile_update(base, idxs, val):
                # Mosaic TC implements neither dynamic_update_slice nor
                # scatter (probed on TPU v5e), so embed the statically-
                # bounded region by lax.pad to tile shape + iota-mask select
                # — pure vector ops. Integer (misc) axes become size-1
                # update axes.
                from jax import lax
                bounds = []
                shape = []
                for s in idxs:
                    if isinstance(s, slice):
                        bounds.append((s.start, s.stop))
                        shape.append(s.stop - s.start)
                    else:
                        bounds.append((s, s + 1))
                        shape.append(1)
                val = val.reshape(tuple(shape))
                pads = [(lo, base.shape[i] - hi, 0)
                        for i, (lo, hi) in enumerate(bounds)]
                padded = lax.pad(val, jnp.array(0, base.dtype), pads)
                mask = None
                for i, (lo, hi) in enumerate(bounds):
                    if lo == 0 and hi == base.shape[i]:
                        continue
                    ax = lax.broadcasted_iota(jnp.int32, base.shape, i)
                    m = (ax >= lo) & (ax < hi)
                    mask = m if mask is None else mask & m
                if mask is None:
                    return padded
                return jnp.where(mask, padded, base)

            ev.gidx_base = {d: pid[lead.index(d)] * block[d] + _goff(d)
                            for d in lead}
            if distributed:
                for di, d in enumerate(dims):
                    ev.gidx_base[d] = ev.gidx_base.get(d, 0) + off_ref[di]

            # ---- skewed-wavefront carry helpers -------------------------
            # Sub-step s writes W_s = [i·B − (s−1)·r, i·B + B − (s−1)·r) in
            # the skewed dim; reading level ℓ at sub-step s needs [W_s.lo −
            # r, …) — below this tile's own computed span.  Those cells are
            # the neighboring tile's freshly-computed right edge: it saved
            # them into the carry, and this tile patches them in before
            # each sub-step (width 2r for a level's first patch — its
            # computed validity starts 2r right of the read edge — then r
            # per later sub-step while it stays live; (D+1)·r total).
            # Single-buffered with a DELAYED save: level ℓ's strip is
            # stored at the top of sub-step min(ℓ+D−1, K−1) — after that
            # sub-step's patches, i.e. after the reader's LAST read of the
            # slot (so no parity double-buffer is needed).
            def _strip_idx(name, lo, width):
                g = program.geoms[name]
                shp = tile_shape(name)
                idxs = []
                for i, (dn, kind) in enumerate(g.axes):
                    if kind == "domain" and dn == sdim:
                        rs_ = resid.get((name, dn), 0)
                        idxs.append(slice(rs_ + lo, rs_ + lo + width))
                    else:
                        idxs.append(slice(0, shp[i]))
                return tuple(idxs)

            def _carry_idx(name, lvl, off, width):
                g = program.geoms[name]
                idxs = [lvl - 1]
                for dn, kind in g.axes:
                    if kind == "domain" and dn == sdim:
                        idxs.append(slice(off, off + width))
                    else:
                        idxs.append(slice(None))
                return tuple(idxs)

            for k in range(K):
                computed: Dict[str, object] = {}
                ev.scratch = {}   # scratch values are per-sub-step
                ev.t = t0_ref[0] + k * dirn

                # patch the live ring levels' left strips from the
                # neighboring tiles' carries before computing sub-step k+1
                if carr_base and k >= 1:
                    r_sk = R[sdim]
                    for n in carr_base:
                        Dn = slots[n]
                        ring = tiles[n]
                        for j in range(len(ring)):
                            lvl = k - (len(ring) - 1 - j)
                            if lvl < 1:
                                continue
                            width = (2 if lvl == k else 1) * r_sk
                            lo = (K - k - 1) * r_sk
                            coff = (lvl + Dn - k - 1) * r_sk
                            strip = carr[carr_base[n]][
                                _carry_idx(n, lvl, coff, width)]
                            # dim start: the left margin is
                            # out-of-domain ghost — zero
                            strip = jnp.where(pid[-1] > 0, strip,
                                              jnp.zeros_like(strip))
                            ring[j] = tile_update(
                                ring[j], _strip_idx(n, lo, width), strip)
                    # delayed saves: store every level whose last patch was
                    # this sub-step's (above) — reads precede the overwrite
                    for n in carr_base:
                        Dn = slots[n]
                        ring = tiles[n]
                        if k < K - 1:
                            lvls = ([k - Dn + 1] if k - Dn + 1 >= 1
                                    else [])
                        else:
                            lvls = list(range(max(1, K - Dn), K))
                        for lvl in lvls:
                            j = Dn - 1 - (k - lvl)
                            lo = block[sdim] + (K - lvl - Dn) * r_sk
                            width = (Dn + 1) * r_sk
                            carr[carr_base[n]][
                                _carry_idx(n, lvl, 0, width)] = \
                                ring[j][_strip_idx(n, lo, width)]

                for si_stage in range(nstages):
                    region = stage_region(k, si_stage)
                    rshape = tuple(hi - lo for lo, hi in region)

                    # global-domain mask over the region's leading dims: in
                    # distributed mode bounds are the GLOBAL problem, so
                    # shard-ghost points keep updating while physical edges
                    # stay zero
                    mask = None
                    for di, d in enumerate(lead):
                        lo, hi = region[di]
                        shape = [1] * len(dims)
                        shape[di] = hi - lo
                        # broadcasted_iota: Mosaic TC crashes on non-lane
                        # 1-D iota (probed on TPU v5e)
                        gidx = (lax.broadcasted_iota(
                                    jnp.int32, tuple(shape), di)
                                + lo + pid[di] * block[d] + _goff(d))
                        if distributed:
                            gidx = gidx + off_ref[di]
                            bound = gdom[d]
                        else:
                            bound = sizes[d]
                        m = (gidx >= 0) & (gidx < bound)
                        mask = m if mask is None else mask & m

                    memo: Dict = {}
                    for part in ana.stages[si_stage].parts:
                        if part.is_scratch:
                            # Scratch eqs evaluate over the stage region
                            # EXPANDED by their write-halo (mirrors
                            # _eval_part's scratch branch; stage_read_widths
                            # already budgeted the margin for the chain) and
                            # persist as full-tile values for offset reads.
                            for eq in part.eqs:
                                ev.misc_env = eq.lhs.misc_vals()
                                name = eq.lhs.var_name()
                                sregion = scratch_region(name, region)
                                ev.region = sregion
                                smemo: Dict = {}   # region differs: own memo
                                val = ev.eval(eq.rhs, tiles, computed, smemo)
                                val = jnp.asarray(val, dtype=dtype)
                                srshape = tuple(hi - lo for lo, hi in sregion)
                                val = jnp.broadcast_to(val, srshape)
                                # partial-dim scratch vars collapse to their
                                # own axes (RHS/cond constant along missing
                                # dims — analysis race rule)
                                val = to_var_region(name, val, sregion)
                                base = ev.scratch.get(
                                    name, jnp.zeros(tile_shape(name), dtype))
                                sidx = region_idxs(name, sregion,
                                                   eq.lhs.misc_vals())
                                if eq.cond is not None:
                                    cm = ev.eval(eq.cond, tiles, computed,
                                                 smemo)
                                    cm = jnp.broadcast_to(cm, srshape)
                                    cm = to_var_region(name, cm, sregion)
                                    val = jnp.where(cm, val, base[sidx])
                                ev.scratch[name] = tile_update(base, sidx, val)
                            continue

                        ev.region = region
                        # misc-as-value evaluates per LHS binding: such parts
                        # memoize per equation (mirrors _eval_part's scoping)
                        part_misc = has_misc_value and any(
                            uses_misc_index(eq.rhs, eq.cond, eq.step_cond)
                            for eq in part.eqs)
                        for eq in part.eqs:
                            if part_misc:
                                memo = {}
                            ev.misc_env = eq.lhs.misc_vals()
                            name = eq.lhs.var_name()
                            lmisc = eq.lhs.misc_vals()
                            val = ev.eval(eq.rhs, tiles, computed, memo)
                            val = jnp.asarray(val, dtype=dtype)
                            val = jnp.broadcast_to(val, rshape)
                            base = computed.get(name, tiles[name][0])
                            base_slice = base[region_idxs(name, region, lmisc)]
                            sel = mask
                            if eq.cond is not None:
                                cm = ev.eval(eq.cond, tiles, computed, memo)
                                cm = jnp.broadcast_to(cm, rshape)
                                sel = cm if sel is None else sel & cm
                            if eq.step_cond is not None:
                                sc = ev.eval(eq.step_cond, tiles, computed,
                                             memo)
                                sc = jnp.broadcast_to(sc, rshape)
                                sel = sc if sel is None else sel & sc
                            # unselected points keep the base (evicted-slot /
                            # earlier-write) values — ghosts there are zero,
                            # so the zero-outside-domain invariant holds.
                            # Partial-dim vars collapse to their own axes
                            # FIRST (the RHS/conditions are constant along
                            # the missing dims — analysis race rule), so the
                            # select runs at var width.
                            val = to_var_region(name, val, region)
                            if sel is not None:
                                sel = to_var_region(name, sel, region)
                                val = jnp.where(sel, val, base_slice)
                            computed[name] = tile_update(
                                base, region_idxs(name, region, lmisc), val)

                # rotate rings with the sub-step's outputs
                for name in written:
                    ring = tiles[name]
                    newest = computed[name]
                    if slots[name] >= 2:
                        tiles[name] = ring[1:] + [newest]
                    else:
                        tiles[name] = [newest]

            # 3) write back the slots the K sub-steps actually produced (the
            #    newest min(K, alloc)); untouched older slots merely shifted
            #    and are rebuilt host-side from the existing padded inputs.
            #    Outputs are PADDED arrays written by manual DMA: BlockSpec
            #    windows cannot express the pad-origin offset, and manual
            #    windows keep sublane offsets 8-aligned. Lane rows ride whole
            #    so lane pads inherit the tile's zeros. The produced value is
            #    first staged into the var's (already consumed) input scratch
            #    tile, because DMA sources must be refs.
            #    NOTE: an output is aliased onto an evicted ring slot only
            #    where no input DMA reads that slot (``reuse_evicted``: the
            #    ``fetch_skipped`` slots) -- a tile DMA fetches its window's
            #    margins from every slot a stage reads, so an in-place
            #    interior write by one grid step would corrupt a later
            #    step's margin reads on real (aliasing) hardware.

            _oi = 0
            for name in written_out:
                ring = tiles[name]
                nback = min(K, slots[name])
                for s in range(nback):
                    val = ring[len(ring) - nback + s]
                    if use_pipe_out:
                        ostage[_oi].at[cur][...] = val
                    else:
                        buf_ref(si_base[name] + s)[...] = val
                    _oi += 1
        if use_strip:
            strip_body()
        else:
            tile_body()
        for cp in out_dmas(pid, cur):
            cp.start()
        if use_pipe_out:
            # the copies stay in flight through the next grid step's
            # compute (retired at step li+2's top, _retire_out); the
            # final step drains the outstanding two parities so the
            # kernel never ends with a DMA in flight
            @pl.when(li == total_steps - 1)
            def _drain_out():
                # use_pipe_out implies total_steps > 1, so the final
                # step always has a predecessor whose copies are the
                # other outstanding parity
                prv_coords = _coords(li - 1)
                for cp in out_dmas(prv_coords, (li - 1) % 2):
                    cp.wait()
                for cp in out_dmas(pid, cur):
                    cp.wait()
        else:
            # staging rides the consumed input scratch: the copies must
            # land before the next grid step re-fills those tiles
            for cp in out_dmas(pid, cur):
                cp.wait()

    # ---- pallas_call assembly -------------------------------------------

    # outputs are full padded arrays written by in-kernel manual DMA
    # (pushed vars have NO outputs — their tiles die in VMEM)
    out_shapes = []
    out_specs = []
    for name in written_out:
        g = program.geoms[name]
        for _ in range(min(K, slots[name])):
            out_shapes.append(jax.ShapeDtypeStruct(tuple(g.shape), dtype))
            out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    nout_total = len(out_shapes)

    # leading scalars (step index, shard offsets) and domain-dim-less
    # vars ride SMEM; DMA-able arrays stay in HBM (ANY)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * nscalars
    for n in var_order:
        space = pltpu.SMEM if n in smem_vars else pl.ANY
        in_specs += [pl.BlockSpec(memory_space=space)] * slots[n]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * n_base
    scratch_shapes = []
    for n in dma_vars:
        for _ in range(slots[n]):
            shp = tile_shape(n)
            if use_pipe:
                shp = (2,) + shp
            scratch_shapes.append(pltpu.VMEM(shp, dtype))
    # skewed-wavefront carry strips persist across the sequential grid
    for n in carr_base:
        scratch_shapes.append(pltpu.VMEM(carry_shape(n), dtype))
    # dedicated parity-doubled output staging (pipelined write-back)
    if use_pipe_out:
        for name in written_out:
            for _ in range(min(K, slots[name])):
                scratch_shapes.append(
                    pltpu.VMEM((2,) + tile_shape(name), dtype))
    # the strip evaluator's own tiles: an explicit result tile for a
    # var whose strips cannot go into the evicted slot's buffer, and
    # the scratch vars' (the whole-tile evaluator holds both as
    # values); inside the work bytes the plan counts
    for n in res_vars + scr_vars:
        scratch_shapes.append(pltpu.VMEM(tile_shape(n), dtype))
    n_arrays = sum(slots[n] for n in dma_vars)
    scratch_shapes.append(pltpu.SemaphoreType.DMA(
        (2, n_arrays) if use_pipe else (n_arrays,)))
    scratch_shapes.append(pltpu.SemaphoreType.DMA(
        (2, max(nout_total, 1)) if use_pipe_out
        else (max(nout_total, 1),)))

    kwargs = {}
    if not interpret:
        # The grid is always sequential ("arbitrary" in every dim): the
        # skew carry rides consecutive steps, staging the outputs
        # reuses the input scratch tiles (racy under megacore
        # partitioning when steps interleave), and the linear-index DMA
        # prefetch additionally requires it.
        # The VMEM limit is raised above Mosaic's 16 MiB default scope
        # (v5e takes ≥120 MiB, probed): tiles budget vmem_budget, live
        # SSA values on top by the capability table's model.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(lead),
            vmem_limit_bytes=vmem_limit_bytes(vmem_budget))
    if n_base:
        kwargs["input_output_aliases"] = {n_inputs + i: i
                                          for i in range(n_base)}
    elif reused:
        kwargs["input_output_aliases"] = {
            nscalars + in_base[n] + j: oi for oi, (n, j) in reused.items()}

    kname = kernel_name(program, K, arm)
    call = pl.pallas_call(
        kernel,
        name=kname,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        **kwargs,
    )

    def run_call(state, t0, offsets, base):
        flat = [jnp.asarray(t0, dtype=jnp.int32).reshape(1)]
        if distributed:
            flat.append(jnp.asarray(offsets, dtype=jnp.int32))
        for n in var_order:
            for a in state[n]:
                flat.append(a.reshape(1) if a.ndim == 0 else a)
        if n_base:
            flat += [a for n in written_out for a in base[n]]
        return call(*flat)

    def written_slots(state, t0, offsets=None, base=None):
        """``{name: [the min(K, slots) arrays this launch writes]}`` for
        the vars the kernel writes out: the only arrays a launch makes
        (under ``onto``, ``base``'s own with its windows written over).
        Everything else of the state (a read-only var, an older ring
        slot that survives the K steps, a pushed var's stale ring) is
        the input's own array, which :func:`merge` puts beside them."""
        outs = run_call(state, t0, offsets, base)
        news = {}
        oi = 0
        for name in written_out:
            g = program.geoms[name]
            news[name] = []
            for s in range(min(K, slots[name])):
                a = outs[oi]
                # outputs come back already padded (no re-pad copy); the
                # lead-dim pad bands are re-zeroed to keep the
                # ghost-zero invariant (lane pads ride whole and inherit
                # tile zeros; window cells outside the global problem —
                # ceil overshoot, skewed-level shift — were masked to
                # zero in-kernel, so zeroing the whole out-of-interior
                # band is equivalent and covers both tilings)
                for dn, kind in g.axes:
                    if kind != "domain" or dn == minor:
                        continue
                    if onto and not (restricted and overshoot[dn]):
                        # the bands keep the zeros ``base`` came with:
                        # no window of a shell reaches them, and a
                        # whole-interior launch writes them only the
                        # zeros the kernel masked
                        continue
                    ax = g.axis_of(dn)
                    o = g.origin[dn]
                    hiw = o + sizes[dn]
                    with jax.named_scope(SCOPE_ZERO_PADS):
                        if o > 0:
                            idx = [slice(None)] * a.ndim
                            idx[ax] = slice(0, o)
                            a = a.at[tuple(idx)].set(0)
                        if hiw < a.shape[ax]:
                            idx = [slice(None)] * a.ndim
                            idx[ax] = slice(hiw, a.shape[ax])
                            a = a.at[tuple(idx)].set(0)
                news[name].append(a)
                oi += 1
        return news

    def merge(state, news):
        """The state after the launch, from references alone: each
        written ring = its surviving (already padded) input slots
        shifted down, plus the newly produced ones; every other entry
        the input's own.  Fresh lists: a ``fuse_vars`` peer holds the
        input's."""
        new_state = dict(state)
        for name, fresh in news.items():
            new_state[name] = list(state[name][len(fresh):]) + list(fresh)
        return new_state

    # pushed vars are ABSENT from the outputs: their rings in the
    # new state keep the (now stale) input arrays — the pipeline
    # runtime never exposes them, and compare/get_var guard them
    def chunk(state, t0, offsets=None):
        return merge(state, written_slots(state, t0, offsets))
    # The two halves, for a launch compiled alone (the one-chip
    # runtime, which builds ``onto`` and donates ``base``): an
    # executable of ``written`` has no output it did not make -- an
    # input handed back as an output is given a buffer of its own and
    # copied, at every launch -- and ``merge`` rebuilds the state on
    # the host.  Inside a program of their own (shard, ensemble,
    # pipeline) callers take ``chunk`` whole.
    chunk.written = written_slots
    chunk.merge = merge
    # how many arrays ``written`` returns of each var: the slots a
    # launch evicts, and what ``base`` holds of it under ``onto``
    written_slots.writes = {n: min(K, slots[n]) for n in written_out}
    # the vars whose arrays ``written`` takes of the state (a launch
    # compiled alone is handed these and no others)
    written_slots.operands = tuple(var_order)

    # jitted alone, either is a module named like its kernel
    # (``jit_yt_<solution>_r<radius>_k<K>``): a device trace puts the
    # pad fusions (and any copy) XLA adds around the call down to it
    written_slots.__name__ = written_slots.__qualname__ = kname
    chunk.__name__ = chunk.__qualname__ = kname

    # Report the tiling ACTUALLY chosen (skew/pipelining can auto-fall
    # back during planning) so the stats model the kernel that runs,
    # not the one eligibility predicted (ADVICE r3).  margin_overhead =
    # redundant computed volume / useful volume per K-group, from the
    # exact per-(sub-step, stage) region widths — the number the skew
    # tiling exists to shrink (reference reports the analogous
    # wave-front overlap in its temporal-tiling stats).
    # scratch_overhead = points of scratch vars evaluated beyond the
    # useful ones, per useful point of those vars, from the regions the
    # kernel above evaluates them over (``scratch_region``: the stage's
    # region grown by the var's write halo), which margin_overhead --
    # one region per (sub-step, stage) -- cannot see when the chain
    # lies inside one stage.
    _useful = _computed = _s_useful = _s_computed = 0
    for _si, _reg in _stage_regions():
        _computed += math.prod(hi - lo for lo, hi in _reg[:-1])
        _useful += math.prod(block[d] for d in lead)
        for _n, _ops in _eq_ops[_si]:
            if _n is None:
                continue
            _sreg = dict(zip(dims, scratch_region(_n, _reg)))
            _own = program.geoms[_n].domain_dims
            _s_computed += math.prod(
                _sreg[d][1] - _sreg[d][0] for d in _own)
            _s_useful += math.prod(
                block.get(d, sizes[d]) for d in _own)
    # points the input tiles fetch beyond the block's own, per useful
    # point: every DMA'd var's tile against its block-sized core
    # (what the DMAs move: each fetched slot's window against its
    # block-sized core, a slot with no DMA in neither)
    _fetched = _core = _lanes = 0
    for _n in dma_vars:
        _lanes = max(_lanes, int(tile_shape(_n)[-1]))
    for (_n, _j), _win in fetch_win.items():
        _fetched += _window_points(
            _n, lambda d, w=_win: w[d][1] - w[d][0])
        _core += _window_points(_n, block.__getitem__)
    # what the output DMAs move: every produced slot's window
    # (``out_copies``' own rows), the minor dim whole
    _stored = sum(
        _window_points(_n, lambda d, n=_n, lvl=_lvl: out_rows(n, lvl, d)[1])
        for _n in written_out
        for _lvl in range(K - min(K, slots[_n]) + 1, K + 1))
    # the two wastes of a shape no block divides and no lane count
    # fills.  edge_overhead = points of the grid's blocks that lie past
    # the domain's edge in the lead dims (evaluated, then masked to
    # zero: ceil coverage, a skewed dim's extra tiles among it) per
    # point of the span; lane_fill = the domain's minor extent over the
    # minor extent of the widest DMA'd tile (the lanes every DMA and
    # every vector op carries that hold no domain: halo, then the pad
    # to a lane multiple)
    _walked = math.prod(span[d] + overshoot[d] for d in lead)
    chunk.tiling = {"fuse_steps": K, "block": dict(block),
                    "kernel": kname,
                    "stages": nstages,
                    # the scratch vars the kernel does not evaluate: read
                    # as arrays filled once (``StepProgram.derive``), and
                    # the step-invariant ones left in-tile, with why
                    "hoisted": list(ana.hoisted),
                    "hoist_kept": dict(ana.hoist_kept),
                    "reach": dict(rad),
                    "stage_consumed": [{d: c[d] for d in lead}
                                       for c in stage_consumed],
                    "grid": list(grid),
                    "interpret": bool(interpret),
                    "skew": bool(use_skew),
                    "skew_dims": list(skew_dims),
                    "push": bool(use_push),
                    "push_vars": list(pushed),
                    "push_tile_bytes": sum(
                        slots[n] * int(math.prod(tile_shape(n))) * esize
                        for n in pushed),
                    "region": ({d: list(region[d]) for d in sorted(restricted)}
                               if restricted else None),
                    "pipeline_dmas": use_pipe,
                    "pipeline_out": use_pipe_out,
                    "tile_bytes": tile_bytes,
                    "result_bytes": _result_bytes(),
                    "budget": vmem_budget,
                    "scoped_need_bytes": scoped_need,
                    "live_factor": live_factor,
                    "vinstr_est": vinstr_est,
                    "growth_ended": growth_ended,
                    "ops_per_point": ops_per_point,
                    "dag_ops_per_point": dag_ops_per_point,
                    "eval": "strip" if use_strip else "tile",
                    "strip": list(strip_shape),
                    "strips": strips,
                    "strip_vregs": strip_vregs,
                    "margin_overhead":
                        round(_computed / max(_useful, 1) - 1, 4),
                    "fetch_overhead":
                        round(_fetched / max(_core, 1) - 1, 4),
                    # by "var/slot": the rows its input DMA copies in
                    # each lead dim, ``[lo, hi)`` in tile coordinates
                    # (``stage_region``'s); the slots no DMA is started
                    # for; the bytes the input DMAs of one launch move
                    # on one device, a step
                    "fetch_windows": {
                        f"{n}/{j}": {d: [lo - resid[n, d], hi - resid[n, d]]
                                     for d, (lo, hi) in win.items()}
                        for (n, j), win in sorted(fetch_win.items())},
                    "fetch_skipped": [f"{n}/{j}" for n, j in fetch_skipped],
                    # the evicted slots this launch's outputs are
                    # written onto (``reuse_evicted``)
                    "reused": [f"{n}/{j}" for n, j in reused.values()],
                    "fetch_bytes_per_step":
                        _fetched * esize * total_steps // K,
                    # and the bytes its output DMAs move, a step
                    "write_bytes_per_step":
                        _stored * esize * total_steps // K,
                    "edge_overhead":
                        round(_walked / math.prod(span[d] for d in lead)
                              - 1, 4),
                    "overshoot": dict(overshoot),
                    "overshoot_pad": dict(overshoot_pad),
                    "lane_fill":
                        round(sizes[minor] / _lanes, 4) if _lanes else 1.0,
                    "scratch_overhead":
                        round(_s_computed / _s_useful - 1, 4)
                        if _s_useful else 0.0,
                    "reasons": list(reasons)}
    return chunk, tile_bytes


def block_sizer(program, fuse_steps: int, **build_args):
    """The build's own accounting of a candidate block (``block ->``
    :class:`~yask_tpu.ops.tile_planner.BlockPrice`) for a
    ``plan_blocks`` call made outside the build, at the tiling
    ``build_args`` resolve to (``skew=``, ``push=``,
    ``unsharded_dims=`` as :func:`build_pallas_chunk` takes them; by
    default the uniform tiling).  Nothing is traced or allocated, and
    the program's pads need not cover the fused halo yet."""
    build_args.setdefault("skew", False)
    return build_pallas_chunk(program, fuse_steps=fuse_steps,
                              plan_only=True, _sizer_only=True,
                              **build_args)


def program_state_slots(program, name: str) -> List[int]:
    g = program.geoms[name]
    n = g.num_slots
    return list(range(n))
