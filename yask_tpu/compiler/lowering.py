"""Lowering: analyzed solutions → executable TPU step programs.

This is the TPU analog of the reference's code generators
(``src/compiler/lib/Cpp.cpp``, ``YaskKernel.cpp``): where the reference emits
intrinsic C++ for nano/pico loops, we build a *traced JAX computation* for a
whole step — XLA then performs the fusion/tiling the reference does by hand.

Key representation choices (each mirrors a reference mechanism):

* **Ring-buffer state.** A var with step dim and step-alloc ``A``
  (``calc_lifespans``, ``Eqs.cpp:1912``) is a list of ``A`` padded arrays
  holding steps ``[t-A+1 … t]``. Writing step ``t+1`` re-uses the evicted
  oldest buffer (the reference's step-index wrapping, ``yk_var.hpp:820``),
  which under ``lax.scan`` + donation is a true in-place rotation.
* **Padded storage + static slices.** Arrays carry left/right pads ≥ halo
  (``update_var_info``, ``setup.cpp:666``); every stencil read is a *static*
  slice of a padded array, which XLA fuses into one loop per part.
* **Masked writes.** Sub-domain/step conditions (``IF_DOMAIN``/``IF_STEP``)
  lower to ``where`` against the evicted buffer's contents, reproducing the
  reference semantics that unwritten points retain stale slot data.
* **Scratch vars** are materialized per step over the domain *expanded by
  their write-halo* (``find_scratch_write_halos``, ``setup.cpp:1044``) and
  die at step end — they never enter the carried state.
* **Array-backend abstraction.** The same lowering executes under numpy
  (eager, independent oracle — the analog of ``run_ref``/``-validate``,
  ``context.cpp:46``) or jnp/XLA (the optimized path).
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from yask_tpu.utils.exceptions import YaskException
from yask_tpu.utils.idx_tuple import IdxTuple
from yask_tpu.compiler.expr import (
    AddExpr,
    AndExpr,
    CompExpr,
    ConstExpr,
    DivExpr,
    EqualsExpr,
    Expr,
    FirstIndexExpr,
    FuncExpr,
    IndexExpr,
    IndexType,
    LastIndexExpr,
    ModExpr,
    MultExpr,
    NegExpr,
    NotExpr,
    NumExpr,
    OrExpr,
    SubExpr,
    VarPoint,
)
from yask_tpu.compiler.analysis import SolutionAnalysis, Part, Stage


# ---------------------------------------------------------------------------
# array-backend adapters
# ---------------------------------------------------------------------------


class ArrayOps:
    """Minimal array-op surface needed by the evaluator."""

    name = "abstract"

    def update(self, arr, idx, val):
        raise NotImplementedError

    def index_array(self, start: int, stop: int, dtype):
        raise NotImplementedError

    def where(self, c, a, b):
        raise NotImplementedError

    def broadcast_to(self, v, shape):
        raise NotImplementedError

    def full(self, shape, val, dtype):
        raise NotImplementedError

    def func(self, name: str, args):
        raise NotImplementedError

    def logical(self, op: str, a, b=None):
        raise NotImplementedError

    def asdtype(self, v, dtype):
        raise NotImplementedError

    def zero_pad(self, v, widths):
        raise NotImplementedError


class JnpOps(ArrayOps):
    name = "jnp"

    def __init__(self):
        import jax.numpy as jnp
        import jax.scipy.special as jsp
        self.jnp = jnp
        self._funcs = {
            "sqrt": jnp.sqrt, "cbrt": jnp.cbrt, "fabs": jnp.abs,
            "erf": jsp.erf, "exp": jnp.exp, "log": jnp.log,
            "atan": jnp.arctan, "sin": jnp.sin, "cos": jnp.cos,
            "tan": jnp.tan, "asin": jnp.arcsin, "acos": jnp.arccos,
            "pow": jnp.power, "max": jnp.maximum, "min": jnp.minimum,
        }

    def update(self, arr, idx, val):
        return arr.at[idx].set(val)

    def index_array(self, start, stop, dtype):
        return self.jnp.arange(start, stop, dtype=self.jnp.int32)

    def where(self, c, a, b):
        return self.jnp.where(c, a, b)

    def broadcast_to(self, v, shape):
        return self.jnp.broadcast_to(v, shape)

    def full(self, shape, val, dtype):
        return self.jnp.full(shape, val, dtype=dtype)

    def func(self, name, args):
        return self._funcs[name](*args)

    def logical(self, op, a, b=None):
        if op == "and":
            return self.jnp.logical_and(a, b)
        if op == "or":
            return self.jnp.logical_or(a, b)
        return self.jnp.logical_not(a)

    def asdtype(self, v, dtype):
        return self.jnp.asarray(v, dtype=dtype)

    def zero_pad(self, v, widths):
        return self.jnp.pad(v, widths)


class NumpyOps(ArrayOps):
    """Eager numpy execution — the independent validation oracle (the role
    of the reference's scalar ``run_ref`` context, ``context.cpp:46``)."""

    name = "numpy"

    def __init__(self):
        import numpy as np
        self.np = np
        try:
            from scipy.special import erf as _erf  # scipy ships with jax
        except Exception:  # pragma: no cover
            _erf = np.vectorize(math.erf)
        self._funcs = {
            "sqrt": np.sqrt, "cbrt": np.cbrt, "fabs": np.abs,
            "erf": _erf, "exp": np.exp, "log": np.log,
            "atan": np.arctan, "sin": np.sin, "cos": np.cos,
            "tan": np.tan, "asin": np.arcsin, "acos": np.arccos,
            "pow": np.power, "max": np.maximum, "min": np.minimum,
        }

    def update(self, arr, idx, val):
        out = arr.copy()
        out[idx] = val
        return out

    def index_array(self, start, stop, dtype):
        return self.np.arange(start, stop, dtype=self.np.int32)

    def where(self, c, a, b):
        return self.np.where(c, a, b)

    def broadcast_to(self, v, shape):
        return self.np.broadcast_to(v, shape)

    def full(self, shape, val, dtype):
        return self.np.full(shape, val, dtype=dtype)

    def func(self, name, args):
        r = self._funcs[name](*args)
        # numpy promotes float32 scalars/arrays to float64 in some funcs;
        # keep the caller responsible for final dtype.
        return r

    def logical(self, op, a, b=None):
        if op == "and":
            return self.np.logical_and(a, b)
        if op == "or":
            return self.np.logical_or(a, b)
        return self.np.logical_not(a)

    def asdtype(self, v, dtype):
        return self.np.asarray(v, dtype=dtype)

    def zero_pad(self, v, widths):
        return self.np.pad(v, widths)


# ---------------------------------------------------------------------------
# var geometry
# ---------------------------------------------------------------------------


def tpu_tile_dims(dtype) -> Tuple[int, int]:
    """(sublane, lane) tile extents of the last two physical axes for
    ``dtype`` (8×128 for f32, 16×128 for bf16) — read from the backend
    capability table so VarGeom's allocation alignment, the pallas DMA
    slab planner, and the checker all consult ONE definition."""
    from yask_tpu.backend import get_capability
    return get_capability().tile_dims(dtype)


class VarGeom:
    """Array geometry for one var: axis order, pads, step allocation —
    the lowered analog of the reference's per-var halo/pad/alloc geometry
    (``yk_var.hpp`` geometry accessors)."""

    def __init__(self, var, ana: SolutionAnalysis, sizes: IdxTuple,
                 extra_pad: Dict[str, Tuple[int, int]],
                 pad_multiple: Optional[Dict[str, int]] = None,
                 dtype="float32", mosaic_align: bool = True):
        self.var = var
        self.name = var.get_name()
        self.has_step = var.step_dim() is not None
        self.alloc = var.get_step_alloc_size() if self.has_step else 1
        # a hoisted scratch var (``SolutionAnalysis.hoisted``) is a
        # read-only array of the step program, filled once by
        # ``StepProgram.derive``
        self.is_derived = self.name in ana.hoisted
        self.is_written = var.is_written and not self.is_derived
        self.is_scratch = var.is_scratch() and not self.is_derived

        # Physical axis order: misc axes FIRST, then domain axes in
        # declared order, step dim removed (step → list position). TPU
        # tiled HBM layouts constrain the last two physical axes
        # (sublane×lane tiles), so domain dims must own them: small misc
        # extents on the lane dim would force 128× over-padding, and the
        # pallas DMA slab rules (see ops/pallas_stencil.py) only hold for
        # domain windows.
        self.axes: List[Tuple[str, str]] = []  # (dim name, kind)
        doms: List[Tuple[str, str]] = []
        for d in var.get_dims():
            if d.type == IndexType.STEP:
                continue
            if d.type.value == "misc":
                self.axes.append((d.name, d.type.value))
            else:
                doms.append((d.name, d.type.value))
        self.axes += doms

        self.domain_dims = [n for n, k in self.axes if k == "domain"]
        self.misc_lo: Dict[str, int] = {}
        self.misc_ext: Dict[str, int] = {}   # DECLARED extent (pre-pad)
        self.shape: List[int] = []
        self.origin: Dict[str, int] = {}   # pad_left per domain dim
        self.pads: Dict[str, Tuple[int, int]] = {}

        # TPU tiling of the last two physical axes: lane tile is 128 for
        # every dtype, sublane tile scales with element width (8 for f32,
        # 16 for bf16). Mosaic DMA windows on tiled memrefs must have
        # tile-aligned sizes and offsets (probed on v5e), so allocations
        # keep lane totals 128-divisible, sublane origins/totals
        # 8-divisible, and sublane right pads carry slack for slab
        # rounding. ``mosaic_align`` applies the rounding — required for
        # the Pallas manual-DMA paths, pure waste on the XLA/ref paths
        # (XLA handles any extent; at 128^3 r=8 the lane round-up alone
        # is +78% footprint and cost the r3 headline 1.8x — VERDICT r3
        # item 4).
        sub_t, lane_t = tpu_tile_dims(dtype)
        nax = len(self.axes)
        lane_ax = nax - 1 if mosaic_align else -99
        sub_ax = nax - 2 if mosaic_align else -99

        def _lcm(a: int, b: int) -> int:
            import math as _m
            return a * b // _m.gcd(a, b)

        wh = ana.scratch_write_halo.get(self.name, {})
        for ai, (n, k) in enumerate(self.axes):
            if k == "domain":
                # (a derived array is padded by its write halo, which
                # holds every offset it is read at)
                hl, hr = (0, 0) if self.is_derived \
                    else var.halo.get(n, (0, 0))
                el, er = extra_pad.get(n, (0, 0))
                wl, wr = wh.get(n, (0, 0))
                pl, pr = hl + wl + el, hr + wr + er
                # Round the allocation up so the padded extent is divisible
                # (sharded mode needs whole-array divisibility; the analog
                # of the reference rounding allocs to vector multiples).
                mult = (pad_multiple or {}).get(n, 1)
                if ai == lane_ax:
                    mult = _lcm(max(mult, 1), lane_t)
                elif ai == sub_ax:
                    pl += (-pl) % sub_t          # aligned origin
                    pr += 2 * sub_t              # slab-rounding slack
                    mult = _lcm(max(mult, 1), sub_t)
                if mult > 1:
                    pr += (-(sizes[n] + pl + pr)) % mult
                self.pads[n] = (pl, pr)
                self.origin[n] = pl
                self.shape.append(sizes[n] + pl + pr)
            else:  # misc
                lo, hi = var.misc_range.get(n, (0, 0))
                self.misc_lo[n] = lo
                ext = hi - lo + 1
                self.misc_ext[n] = ext
                # misc axes in the tiled (last-two) positions only occur
                # on vars WITH domain dims (a single-domain-dim var keeps
                # misc at its sublane) — those are DMA'd whole, so the
                # extent must be tile-aligned. Vars with no domain dims
                # ride SMEM on the pallas path and stay unpadded.
                if self.domain_dims:
                    if ai == lane_ax:
                        ext += (-ext) % lane_t
                    elif ai == sub_ax:
                        ext += (-ext) % sub_t
                self.shape.append(ext)

    @property
    def num_slots(self) -> int:
        """Ring slots allocated in state: write-back-optimized alloc for
        written step vars, one slot otherwise. THE single definition —
        shard_map in_specs, pallas ring handling, and tile planning must
        all agree with ``alloc_state`` or the shard pytree structure
        desynchronizes from the state rings at trace time."""
        return self.alloc if (self.has_step and self.is_written) else 1

    def axis_of(self, dim: str) -> int:
        for i, (n, _) in enumerate(self.axes):
            if n == dim:
                return i
        raise YaskException(f"var '{self.name}' has no dim '{dim}'")


# ---------------------------------------------------------------------------
# step program
# ---------------------------------------------------------------------------


class StepProgram:
    """An executable step function for fixed domain sizes.

    ``state`` is ``{var_name: [array, ...]}`` where the list is the
    step-ring (oldest→newest; length = step-alloc; length 1 for stepless
    vars). ``step(state, t)`` returns the new state after one step.
    """

    def __init__(self, csol: "CompiledSolution", sizes: IdxTuple,
                 extra_pad: Optional[Dict[str, Tuple[int, int]]] = None,
                 ops: Optional[ArrayOps] = None,
                 rank_offset: Optional[Dict[str, int]] = None,
                 global_sizes: Optional[IdxTuple] = None,
                 pad_multiple: Optional[Dict[str, int]] = None,
                 mosaic_align: bool = True):
        self.csol = csol
        ana = self.ana = csol.ana
        self.soln = csol.soln
        self.sizes = sizes.copy()
        self.ops = ops or JnpOps()
        self.dtype = csol.dtype
        extra_pad = extra_pad or {}
        # Local-interior origin in global coordinates (0 on single device;
        # the shard offset under shard_map — reference rank offsets,
        # setup.cpp:169).
        self.rank_offset = dict(
            rank_offset or {d: 0 for d in self.ana.domain_dims})
        gsz = global_sizes if global_sizes is not None else sizes
        self.global_first = {d: 0 for d in ana.domain_dims}
        self.global_last = {d: gsz[d] - 1 for d in ana.domain_dims}

        self.mosaic_align = mosaic_align
        self._zero_extend = False    # see derive
        self.geoms: Dict[str, VarGeom] = {}
        for v in self.soln.get_vars():
            self.geoms[v.get_name()] = VarGeom(v, self.ana, sizes, extra_pad,
                                               pad_multiple,
                                               dtype=self.dtype,
                                               mosaic_align=mosaic_align)

        # Stage metadata for halo exchange / fused-tile margin accounting
        # (the dirty-width analog of the reference's per-var dirty flags,
        # yk_var.hpp:564; see SolutionAnalysis.stage_read_widths).
        # one equation scan: the union form derives from the split form
        self.stage_reads_split = self.ana.stage_read_widths_split()
        self.stage_reads = []
        for kinds in self.stage_reads_split:
            reads: Dict[str, Dict[str, Tuple[int, int]]] = {}
            for kind in ("ring", "computed"):
                for vname, widths in kinds[kind].items():
                    entry = reads.setdefault(vname, {})
                    for d, (l, r) in widths.items():
                        cl, cr = entry.get(d, (0, 0))
                        entry[d] = (max(cl, l), max(cr, r))
            self.stage_reads.append(reads)

    # -- state construction ------------------------------------------------

    def alloc_state(self, init: Optional[Dict[str, object]] = None):
        """Allocate the state dict; arrays zero-filled unless ``init``
        provides full padded arrays or callables(shape)->array.  A
        hoisted scratch var's array is not allocated here: its first
        fill (:meth:`derive`) creates it."""
        import numpy as np
        state: Dict[str, List[object]] = {}
        for name, g in self.geoms.items():
            if g.is_scratch or g.is_derived:
                continue
            nslots = g.num_slots
            arrs = []
            for _ in range(nslots):
                if init and name in init:
                    a = init[name]
                    a = a(tuple(g.shape)) if callable(a) else np.asarray(a)
                    if tuple(a.shape) != tuple(g.shape):
                        raise YaskException(
                            f"init for '{name}' has shape {a.shape}, "
                            f"expected {tuple(g.shape)}")
                    arrs.append(self.ops.asdtype(a, self.dtype))
                else:
                    arrs.append(self.ops.full(tuple(g.shape), 0.0, self.dtype))
            state[name] = arrs
        return state

    def hbm_bytes_per_point(self, fuse_steps: int = 1,
                            block: Optional[Dict[str, int]] = None,
                            skew=False
                            ) -> Tuple[float, float]:
        """Modeled HBM traffic per interior point per STEP as
        ``(read_bytes, write_bytes)`` — the roofline yardstick next to
        est-FLOPS (reference reads/writes-per-point report,
        ``soln_apis.cpp:536-551``, recast at array granularity: a fused
        XLA/Pallas step reads each live (var, ring-slot) array once and
        writes each produced slot once; scratch vars never leave VMEM).
        ``fuse_steps``/``block`` model the pallas K-group: reads pay the
        tile-halo overlap factor and amortize over K.  ``skew`` models
        the streaming skewed wavefront: each skewed blocked dim fetches
        (K+1)·r + E of margin instead of 2·K·r (the inter-tile strips
        ride the VMEM carry).  Accepts the legacy bool (True = the
        innermost blocked dim) or the per-dim form — a collection of
        dim names, as reported by ``chunk.tiling['skew_dims']``."""
        import numpy as np
        esize = np.dtype(self.dtype).itemsize
        dompts = 1
        for d in self.ana.domain_dims:
            dompts *= self.sizes[d]
        K = max(1, fuse_steps)
        rad = self.ana.fused_step_radius()
        lead = self.ana.domain_dims[:-1]
        sdim = lead[-1] if lead else None
        if isinstance(skew, (list, tuple, set, frozenset)):
            skew_dims = set(skew)
        else:
            skew_dims = {sdim} if (skew and sdim is not None) else set()
        rd = 0.0
        wr = 0.0
        unread = self.ana.derive_only
        for name, g in self.geoms.items():
            if g.is_scratch or name in unread:
                continue
            cells = 1
            for ext in g.shape:
                cells *= ext
            # fused-tile halo overlap on the lead dims actually blocked
            ov = 1.0
            if block:
                num = den = 1.0
                for d in lead:
                    if d in g.domain_dims and block.get(d):
                        if d in skew_dims:
                            # only the sublane (stream) dim pays E_sk:
                            # misaligned radii add 2·sub_t of computed
                            # right margin (see pallas_stencil); outer
                            # skewed dims are untiled (E = 0)
                            r_ = rad.get(d, 0)
                            e_ = 0
                            if d == sdim:
                                sub_t = tpu_tile_dims(self.dtype)[0]
                                e_ = 2 * sub_t if r_ % sub_t else 0
                            num *= block[d] + (K + 1) * r_ + e_
                        else:
                            num *= block[d] + 2 * rad.get(d, 0) * K
                        den *= block[d]
                ov = num / max(den, 1.0)
            rd += g.num_slots * cells * ov
            if g.is_written:
                wr += min(K, g.num_slots) * cells
        return (esize * rd / (dompts * K), esize * wr / (dompts * K))

    # -- expression evaluation --------------------------------------------

    def _region_shape(self, region: Dict[str, Tuple[int, int]]) -> Tuple[int, ...]:
        return tuple(region[d][1] - region[d][0] for d in self.ana.domain_dims)

    def _read_point(self, p: VarPoint, region, state, computed, scratch_vals):
        """Slice a var access over ``region`` (coords relative to the local
        interior origin) into an array broadcast over the region shape."""
        g = self.geoms[p.var_name()]
        ofs = p.domain_offsets()
        misc = p.misc_vals()
        so = p.step_offset()

        # Choose the source array.
        if g.is_scratch:
            if p.var_name() not in scratch_vals:
                raise YaskException(
                    f"scratch var '{p.var_name()}' read before written")
            arr, sc_origin = scratch_vals[p.var_name()]
        else:
            ring = state[p.var_name()]
            if so is not None and g.has_step and g.is_written \
                    and so == self.ana.step_dir:
                # Reading the value being computed this step.
                if p.var_name() in computed:
                    arr = computed[p.var_name()]
                else:
                    raise YaskException(
                        f"'{p.var_name()}' read at the written step before "
                        "any equation computed it (ordering bug)")
            elif g.has_step and g.is_written:
                s = so if so is not None else 0
                # ring holds steps [t-A+1 .. t]; offset s ≤ 0 → index A-1+s
                # (mirrored for negative step_dir).
                idx = len(ring) - 1 + s * self.ana.step_dir
                if not (0 <= idx < len(ring)):
                    raise YaskException(
                        f"step offset {s} of '{p.var_name()}' outside its "
                        f"allocation {g.alloc}")
                arr = ring[idx]
            else:
                arr = ring[0]
            sc_origin = None

        # Build the index tuple in the var's axis order.
        idxs = []
        beyond = []     # rows read past each end of a domain axis
        for n, kind in g.axes:
            if kind == "misc":
                idxs.append(misc[n] - g.misc_lo[n])
            else:
                a, b = region[n]
                o = ofs.get(n, 0)
                if sc_origin is not None:
                    base = sc_origin[n]
                else:
                    base = g.origin[n]
                lo = base + a + o
                hi = base + b + o
                ext = g.shape[g.axis_of(n)]
                if (lo < 0 or hi > ext) and not self._zero_extend:
                    raise YaskException(
                        f"read of '{p.var_name()}' dim {n} offset {o} over "
                        f"[{a},{b}) exceeds padded array (pad too small)")
                beyond.append((max(-lo, 0), max(hi - ext, 0)))
                idxs.append(slice(max(lo, 0), min(hi, ext)))
        out = arr[tuple(idxs)]
        if any(w != (0, 0) for w in beyond):
            # ``derive`` alone: past an array's end lie the zeros its own
            # ghost cells hold
            out = self.ops.zero_pad(out, beyond)

        # Broadcast into solution domain-dim order over the region.
        # out currently has one axis per var domain dim, in var order.
        tgt_shape = self._region_shape(region)
        var_ddims = [n for n, k in g.axes if k == "domain"]
        if var_ddims != self.ana.domain_dims:
            # transpose var order → solution order (of present dims),
            # then insert singleton axes for missing dims.
            present = [d for d in self.ana.domain_dims if d in var_ddims]
            perm = [var_ddims.index(d) for d in present]
            if perm != list(range(len(perm))):
                out = out.transpose(perm)
            shape = []
            k = 0
            for d in self.ana.domain_dims:
                if d in var_ddims:
                    shape.append(region[d][1] - region[d][0])
                    k += 1
                else:
                    shape.append(1)
            out = out.reshape(tuple(shape))
            out = self.ops.broadcast_to(out, tgt_shape)
        return out

    def _eval(self, e: Expr, region, t, state, computed, scratch_vals, memo):
        # Structural memo key: common subexpressions are traced once per
        # part even across equations (the reference's CSE pass,
        # ExprUtils.hpp:77, done here as hash-consing at eval time).
        key = e.skey()
        if key in memo:
            return memo[key]
        ops = self.ops
        ev = lambda x: self._eval(x, region, t, state, computed,
                                  scratch_vals, memo)
        if isinstance(e, ConstExpr):
            r = e.value
        elif isinstance(e, IndexExpr):
            if e.type == IndexType.STEP:
                r = t
            elif e.type == IndexType.DOMAIN:
                a, b = region[e.name]
                # rank_offset may be a traced scalar (lax.axis_index-derived
                # under shard_map), so keep the arange static and add it.
                off = self.rank_offset[e.name]
                iarr = ops.index_array(a, b, None)
                shape = [1] * len(self.ana.domain_dims)
                ax = self.ana.domain_dims.index(e.name)
                shape[ax] = b - a
                r = iarr.reshape(tuple(shape)) + off
            else:
                # A misc index used as a VALUE is the current equation's
                # pinned LHS misc index — a per-equation constant
                # (reference generated code inlines it). Never memoized:
                # the same node appears in sibling equations with
                # different LHS bindings.
                mv = getattr(self, "_cur_misc", None) or {}
                if e.name not in mv:
                    raise YaskException(
                        f"misc index '{e.name}' used as a value outside "
                        "an equation that pins it on the LHS")
                return mv[e.name]
        elif isinstance(e, FirstIndexExpr):
            r = self.global_first[e.dim.name]
        elif isinstance(e, LastIndexExpr):
            r = self.global_last[e.dim.name]
        elif isinstance(e, VarPoint):
            r = self._read_point(e, region, state, computed, scratch_vals)
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
            r = paired_func_eval(ops.func, e, [ev(a) for a in e.args],
                                 memo, getattr(self.ana, "sincos_args",
                                               ()))
        elif isinstance(e, CompExpr):
            a, b = ev(e.lhs), ev(e.rhs)
            r = {"==": lambda: a == b, "!=": lambda: a != b,
                 "<": lambda: a < b, "<=": lambda: a <= b,
                 ">": lambda: a > b, ">=": lambda: a >= b}[e.op]()
        elif isinstance(e, AndExpr):
            r = ops.logical("and", ev(e.lhs), ev(e.rhs))
        elif isinstance(e, OrExpr):
            r = ops.logical("or", ev(e.lhs), ev(e.rhs))
        elif isinstance(e, NotExpr):
            r = ops.logical("not", ev(e.arg))
        else:  # pragma: no cover
            raise YaskException(f"cannot evaluate node {type(e).__name__}")
        memo[key] = r
        return r

    # -- equation / part / stage evaluation -------------------------------

    def _interior_region(self) -> Dict[str, Tuple[int, int]]:
        return {d: (0, self.sizes[d]) for d in self.ana.domain_dims}

    def _to_var_layout(self, val, g: VarGeom, region):
        """Convert a value computed in solution domain-dim order over
        ``region`` into the target var's own axis order, dropping dims the
        var lacks (the RHS must be constant along those — index 0 taken)
        and transposing when the var declares its dims in another order."""
        shape = self._region_shape(region)
        val = self.ops.broadcast_to(val, shape)
        sol = self.ana.domain_dims
        var_dd = g.domain_dims
        if var_dd == sol:
            return val
        idx = tuple(slice(None) if d in var_dd else 0 for d in sol)
        val = val[idx]
        present = [d for d in sol if d in var_dd]
        perm = [present.index(d) for d in var_dd]
        if perm != list(range(len(perm))):
            val = val.transpose(perm)
        return val

    def _eval_part(self, part: Part, t, state, computed, scratch_vals,
                   over: Optional[Dict[str, Tuple[int, int]]] = None):
        """Evaluate a part; ``over`` restricts evaluation to a sub-region
        of the interior (interior coords) — the basis of the
        interior/exterior overlap split (reference ``MpiSection``,
        ``context.hpp:789-833``)."""
        ops = self.ops
        base_region = over if over is not None else self._interior_region()
        if part.is_scratch:
            # Evaluate over the (sub-)region expanded by the write-halo.
            for eq in part.eqs:
                self._cur_misc = eq.lhs.misc_vals()
                g = self.geoms[eq.lhs.var_name()]
                wh = self.ana.scratch_write_halo.get(g.name, {})
                region = {}
                for d in self.ana.domain_dims:
                    wl, wr = wh.get(d, (0, 0))
                    if d in g.domain_dims:
                        a, b = base_region[d]
                        region[d] = (a - wl, b + wr)
                    else:
                        region[d] = (0, 1)  # scratch lacks this dim? rare
                memo: Dict = {}
                val = self._eval(eq.rhs, region, t, state, computed,
                                 scratch_vals, memo)
                val = self._to_var_layout(
                    ops.asdtype(val, self.dtype), g, region)
                if eq.cond is not None:
                    mask = self._eval(eq.cond, region, t, state, computed,
                                      scratch_vals, memo)
                    mask = self._to_var_layout(mask, g, region)
                    old = scratch_vals.get(g.name)
                    base = old[0] if old else \
                        ops.full(val.shape, 0.0, self.dtype)
                    val = ops.where(mask, val, base)
                origin = {d: -region[d][0] for d in self.ana.domain_dims
                          if d in g.domain_dims}
                scratch_vals[g.name] = (val, origin)
            return

        region = base_region
        # One memo across the whole part: no eq in a part reads a var the
        # part writes (parts have no internal deps), so cached reads stay
        # valid and duplicated subtrees across equations trace once.
        # Exception: misc-index-as-value expressions evaluate differently
        # per equation (LHS binding), so such parts memoize per equation.
        from yask_tpu.compiler.expr import uses_misc_index
        part_misc = any(uses_misc_index(eq.rhs, eq.cond, eq.step_cond)
                        for eq in part.eqs)
        memo: Dict = {}
        for eq in part.eqs:
            if part_misc:
                memo = {}
            self._cur_misc = eq.lhs.misc_vals()
            name = eq.lhs.var_name()
            g = self.geoms[name]
            ring = state[name]
            base_arr = computed.get(name, ring[0])  # evicted slot is base
            val = self._eval(eq.rhs, region, t, state, computed,
                             scratch_vals, memo)
            val = self._to_var_layout(ops.asdtype(val, self.dtype), g, region)

            # Written-region index tuple in the var's own axis order.
            idxs = []
            misc = eq.lhs.misc_vals()
            for n, kind in g.axes:
                if kind == "misc":
                    idxs.append(misc[n] - g.misc_lo[n])
                else:
                    a, b = region[n]
                    idxs.append(slice(g.origin[n] + a, g.origin[n] + b))

            cond_mask = None
            if eq.cond is not None:
                cond_mask = self._eval(eq.cond, region, t, state, computed,
                                       scratch_vals, memo)
            if eq.step_cond is not None:
                sc = self._eval(eq.step_cond, region, t, state, computed,
                                scratch_vals, memo)
                cond_mask = sc if cond_mask is None else \
                    ops.logical("and", cond_mask, sc)
            if cond_mask is not None:
                old_val = base_arr[tuple(idxs)]
                mask = self._to_var_layout(cond_mask, g, region)
                val = ops.where(mask, val, old_val)

            computed[name] = ops.update(base_arr, tuple(idxs), val)

    def derive(self, state):
        """``{name: [array]}`` of the hoisted scratch vars
        (``SolutionAnalysis.hoisted``): each of their equations
        evaluated ONCE, from the read-only arrays of ``state``, over the
        var's WHOLE padded extent -- so a ghost cell holds ``f(source's
        ghost)`` (``cos(0) = 1`` outside the domain), what the in-tile
        evaluation of a scratch var computes there, and every cell a
        kernel's window can reach is filled.  A source is read as zeros
        past the end of its own array, where its ghost cells' zeros
        would go on.  Nothing may re-zero a derived array's pads after
        this."""
        ops = self.ops
        dims = self.ana.domain_dims
        state = dict(state)
        out: Dict[str, List[object]] = {}
        self._zero_extend = True
        try:
            for eq in self.ana.derive_eqs:
                self._cur_misc = {}
                g = self.geoms[eq.lhs.var_name()]
                region = {d: (-g.origin[d],
                              g.shape[g.axis_of(d)] - g.origin[d])
                          for d in dims}
                memo: Dict = {}
                val = self._eval(eq.rhs, region, None, state, {}, {}, memo)
                val = self._to_var_layout(
                    ops.asdtype(val, self.dtype), g, region)
                if eq.cond is not None:
                    mask = self._eval(eq.cond, region, None, state, {}, {},
                                      memo)
                    base = out[g.name][0] if g.name in out else \
                        ops.full(val.shape, 0.0, self.dtype)
                    val = ops.where(self._to_var_layout(mask, g, region),
                                    val, base)
                out[g.name] = state[g.name] = [val]
        finally:
            self._zero_extend = False
        return out

    def eval_stage(self, stage_idx: int, t, state, computed, scratch_vals,
                   over: Optional[Dict[str, Tuple[int, int]]] = None):
        """Evaluate one stage in place on (computed, scratch_vals);
        ``over`` restricts to a sub-region (overlap split)."""
        for part in self.ana.stages[stage_idx].parts:
            self._eval_part(part, t, state, computed, scratch_vals,
                            over=over)

    def step(self, state, t, halo_hook: Optional[Callable] = None):
        """Advance the solution by one step; returns the new state.

        ``halo_hook(stage_idx, state, computed)`` is called before each
        stage — the distributed runtime injects ghost-cell exchange there
        (the reference's between-stage ``exchange_halos``,
        ``context.cpp:438``).
        """
        computed: Dict[str, object] = {}
        scratch_vals: Dict[str, Tuple[object, Dict[str, int]]] = {}
        for si in range(len(self.ana.stages)):
            if halo_hook is not None:
                state, computed = halo_hook(si, state, computed)
            self.eval_stage(si, t, state, computed, scratch_vals)
        # Rotate rings.
        new_state: Dict[str, List[object]] = {}
        for name, ring in state.items():
            g = self.geoms[name]
            if name in computed:
                if g.has_step:
                    new_state[name] = list(ring[1:]) + [computed[name]]
                else:
                    new_state[name] = [computed[name]]
            else:
                new_state[name] = list(ring)
        return new_state


class CompiledSolution:
    """A solution lowered for TPU execution (what the reference's generated
    ``.so`` is: the thing ``yk_factory::new_solution`` instantiates).

    Holds the analysis and dtype; :meth:`plan` binds domain sizes/pads and
    returns a :class:`StepProgram`.
    """

    def __init__(self, soln, analysis: SolutionAnalysis,
                 dtype: Optional[object] = None):
        self.soln = soln
        self.ana = analysis
        if dtype is None:
            import numpy as np
            eb = soln.get_settings().elem_bytes
            try:
                import jax.numpy as jnp
                dtype = {2: jnp.bfloat16, 4: np.float32, 8: np.float64}[eb]
            except ImportError:  # pragma: no cover
                dtype = {2: np.float16, 4: np.float32, 8: np.float64}[eb]
        self.dtype = dtype

    def plan(self, sizes: IdxTuple, ops: Optional[ArrayOps] = None,
             extra_pad: Optional[Dict[str, Tuple[int, int]]] = None,
             rank_offset: Optional[Dict[str, int]] = None,
             global_sizes: Optional[IdxTuple] = None,
             pad_multiple: Optional[Dict[str, int]] = None,
             mosaic_align: bool = True) -> StepProgram:
        for d in self.ana.domain_dims:
            if not sizes.has_dim(d):
                raise YaskException(f"domain size for dim '{d}' not given")
        return StepProgram(self, sizes, extra_pad=extra_pad, ops=ops,
                           rank_offset=rank_offset, global_sizes=global_sizes,
                           pad_multiple=pad_multiple,
                           mosaic_align=mosaic_align)
