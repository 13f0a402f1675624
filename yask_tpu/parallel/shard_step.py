"""Explicit sharded stepping: ``shard_map`` + ``lax.ppermute`` ghost exchange.

The structural twin of the reference's MPI halo-exchange machinery
(``src/kernel/lib/halo.cpp``): per-var, per-dim edge slabs are sent to
neighbor shards before each stage that reads them — but expressed as XLA
collective-permutes over ICI inside a ``shard_map``, so the compiler's
latency-hiding scheduler overlaps them with compute (replacing the
reference's interior/exterior split + ``MPI_Test`` progress pump,
``context.cpp:377-478``, ``halo.cpp:494``).

Design notes mapping to the reference:

* *dirty tracking* (``yk_var.hpp:564``): statically resolved — the exchange
  set per stage comes from ``StepProgram.stage_reads`` (which vars are read
  with nonzero offsets), so only stale ghosts are exchanged, and each ring
  slot is exchanged exactly once per step (older slots were refreshed when
  they were newest).
* *shm/device-direct paths* (``halo.cpp:33-66``): collapsed — ICI is the
  only transport, and XLA picks the best implementation.
* *non-periodic boundaries*: ``ppermute`` members that receive nothing get
  zeros, matching this runtime's zero-filled physical-boundary ghosts.
* *where the state rests*: a shard program computes on per-shard PADDED
  arrays (ghost rows, physical-boundary zeros, Mosaic's alignment:
  :class:`RestGeom`), takes them as its donated argument and hands the
  last group's back, and that is the form the state rests in between
  calls (``RunState.padded``).  The one pad (``yt_shard_pad``) and the
  one strip (``yt_shard_strip``) of a shard state are programs of their
  own (:func:`pad_shards` / :func:`strip_shards`) that run only when
  something other than the next launch asks: a first call, a host
  access (``StencilContext._resident``), another K or rank grid
  (:func:`rest_padded`).  A call boundary is one more hand-over of the
  K-group loop: ghost rows at rest hold the last exchange's values and
  are rewritten by the next call's up-front exchange before a kernel
  reads them.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from yask_tpu.cache import aot_compile
from yask_tpu.obs.metrics import get_registry
from yask_tpu.obs.tracer import span
from yask_tpu.utils.exceptions import YaskException


class _TraceStats:
    """Trace-time collective counter: every ppermute the exchange paths
    issue bumps ``nperm`` while the program is being traced/lowered,
    so the delta around a build is the collective count of the schedule
    that actually compiled (model-free) — the number the coalescing A/B
    exists to move.

    ``by_axis`` counts, the same way, every edge slab the exchange
    paths cut for sending and its bytes as sent (the pads of the other
    axes included), by mesh axis and direction: ``(dim, towards the
    higher rank) -> (slabs, bytes)``.  A shard program is one SPMD
    trace, so both directions of an axis are cut whatever the shard:
    their sum is what a shard with a neighbour on BOTH sides would send
    (an edge shard's slab towards the physical boundary goes to no one;
    in a 2-wide axis every shard is an edge shard), and
    ``_launch_attrs`` says beside it what a shard that exists sends.
    The shard programs read the delta around each exchange round they
    trace (``mark`` / ``since``) and put the launch's totals on its
    ``run.launch`` span."""

    def __init__(self):
        self.nperm = 0
        self.by_axis: Dict[Tuple[str, bool], Tuple[int, int]] = {}

    def sent(self, slab, dim: str, up: bool) -> None:
        n, b = self.by_axis.get((dim, up), (0, 0))
        self.by_axis[dim, up] = (
            n + 1, b + int(slab.size) * slab.dtype.itemsize)

    def mark(self):
        return dict(self.by_axis)

    def since(self, mark):
        """``{(dim, up): (slabs, bytes)}`` counted since ``mark``."""
        out = {}
        for key, (n, b) in self.by_axis.items():
            n0, b0 = mark.get(key, (0, 0))
            if n > n0:
                out[key] = (n - n0, b - b0)
        return out


_trace_stats = _TraceStats()

#: ``jax.named_scope`` names of the shard programs' XLA-side work, so
#: a device trace tells exchange pack/unpack (of each mesh axis: both
#: names end in the axis the slab crosses, ``_x``, ``_y``) and ghost
#: padding from the kernels by the program's names
SCOPE_PACK = "yt_exchange_pack"        # slab slices / concatenation
SCOPE_UNPACK = "yt_exchange_unpack"    # received slabs into the ghosts
SCOPE_PAD = "yt_shard_pad"             # interiors -> padded shards
SCOPE_STRIP = "yt_shard_strip"         # padded shards -> interiors


def exchange_ghosts(arr, geom, dim_widths: Dict[str, Tuple[int, int]],
                    nr, local_sizes):
    """Fill ``arr``'s ghost pads from neighbor shards for the given dims.

    ``arr`` is a locally-padded shard array; for each dim with width (l, r):
    my right-interior edge slab -> right neighbor's left ghost, and vice
    versa (the pack/send/unpack cycle of ``exchange_halos``, ``halo.cpp:146``
    collapsed into two ppermutes per dim).
    """
    import jax
    from jax import lax
    for d, (l, r) in dim_widths.items():
        n = nr.get(d, 1)
        if n <= 1 or d not in geom.domain_dims:
            continue
        ax = geom.axis_of(d)
        o = geom.origin[d]
        sz = local_sizes[d]
        for up, width, lo, at, perm in (
                (True, l, o + sz - l, o - l,
                 [(i, i + 1) for i in range(n - 1)]),
                (False, r, o, o + sz,
                 [(i + 1, i) for i in range(n - 1)])):
            if width <= 0:
                continue
            with jax.named_scope(f"{SCOPE_PACK}_{d}"):
                slab = lax.slice_in_dim(arr, lo, lo + width, axis=ax)
            _trace_stats.sent(slab, d, up)
            _trace_stats.nperm += 1
            recv = lax.ppermute(slab, d, perm)
            with jax.named_scope(f"{SCOPE_UNPACK}_{d}"):
                arr = lax.dynamic_update_slice_in_dim(arr, recv, at,
                                                      axis=ax)
    return arr


def _exchange_coalesced(items, nr, local_sizes, order):
    """Coalesced ghost exchange: ONE ppermute per (mesh axis, direction)
    carrying every buffer's slab, flattened and concatenated (grouped by
    dtype), then split/reshaped back into each buffer's ghost band.

    ``ppermute`` only moves bytes, so the result is bit-identical to
    per-buffer collectives; axes still go strictly in plan order, so the
    corner composition (a later axis's slab spans the earlier axes'
    freshly filled ghosts) is preserved — diagonal ghosts keep arriving
    without dedicated collectives.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    arrs = [a for a, _g, _w in items]
    metas = [(g, w) for _a, g, w in items]
    for d in order:
        n = nr.get(d, 1)
        if n <= 1:
            continue
        sz = local_sizes[d]
        pack, unpack = f"{SCOPE_PACK}_{d}", f"{SCOPE_UNPACK}_{d}"
        for left in (True, False):
            perm = ([(i, i + 1) for i in range(n - 1)] if left
                    else [(i + 1, i) for i in range(n - 1)])
            # dtype -> (flattened slabs, (item idx, axis, write pos,
            # slab shape, element count))
            groups: Dict[str, Tuple[list, list]] = {}
            for i, (g, w) in enumerate(metas):
                if d not in w or d not in g.domain_dims:
                    continue
                wl, wr = w[d]
                width = wl if left else wr
                if width <= 0:
                    continue
                ax = g.axis_of(d)
                o = g.origin[d]
                lo = (o + sz - width) if left else o
                with jax.named_scope(pack):
                    slab = lax.slice_in_dim(arrs[i], lo, lo + width,
                                            axis=ax)
                _trace_stats.sent(slab, d, left)
                wr_at = (o - width) if left else (o + sz)
                slabs, meta = groups.setdefault(str(slab.dtype),
                                                ([], []))
                slabs.append(slab)
                meta.append((i, ax, wr_at, slab.shape,
                             int(np.prod(slab.shape))))
            for slabs, meta in groups.values():
                if len(slabs) == 1:
                    # single payload: nothing to pack
                    i, ax, wr_at, _shp, _n = meta[0]
                    _trace_stats.nperm += 1
                    recv = lax.ppermute(slabs[0], d, perm)
                    with jax.named_scope(unpack):
                        arrs[i] = lax.dynamic_update_slice_in_dim(
                            arrs[i], recv, wr_at, axis=ax)
                    continue
                with jax.named_scope(pack):
                    payload = jnp.concatenate(
                        [jnp.reshape(s, (-1,)) for s in slabs])
                _trace_stats.nperm += 1
                recv = lax.ppermute(payload, d, perm)
                off = 0
                with jax.named_scope(unpack):
                    for i, ax, wr_at, shp, nel in meta:
                        part = jnp.reshape(
                            lax.slice_in_dim(recv, off, off + nel,
                                             axis=0), shp)
                        off += nel
                        arrs[i] = lax.dynamic_update_slice_in_dim(
                            arrs[i], part, wr_at, axis=ax)
    return arrs


def exchange_many(items, nr, local_sizes, plan=None):
    """The one multi-buffer exchange entry both shard paths trace.

    ``items`` is a list of ``(padded array, geom, dim_widths)``; returns
    the exchanged arrays in the same order.  The CommPlan decides the
    schedule: without one (or with coalescing off) each buffer runs the
    serial per-buffer :func:`exchange_ghosts` with its width dims
    reordered to the plan; with coalescing on, all slabs for one (axis,
    direction) ride a single concatenated ppermute
    (``_exchange_coalesced``).
    Either way axes go in plan order, so corner ghosts stay composed
    exchanges and both schedules are bit-identical.
    """
    if not items:
        return []
    order = list(plan.order) if plan is not None else []
    seen = set(order)
    for _a, _g, w in items:
        for d in w:
            if d not in seen:
                order.append(d)
                seen.add(d)
    if plan is None or not plan.coalesce:
        out = []
        for a, g, w in items:
            ww = {d: w[d] for d in order if d in w}
            out.append(exchange_ghosts(a, g, ww, nr, local_sizes))
        return out
    return _exchange_coalesced(items, nr, local_sizes, order)


def _widen(applied: Dict, key, widths: Dict[str, Tuple[int, int]]):
    """Track the union of exchanged ghost widths per buffer: returns
    (union, grew) where ``grew`` means this refresh must actually run —
    a later stage reading the same buffer with WIDER ghosts re-exchanges
    the union, not the narrow refresh. Shared by both shard paths'
    refresh hooks so the tracking cannot drift."""
    out = dict(applied.get(key, {}))
    grew = key not in applied
    for d, (l, r) in widths.items():
        al, ar = out.get(d, (0, 0))
        if l > al or r > ar:
            grew = True
        out[d] = (max(al, l), max(ar, r))
    return out, grew


def overlap_decision(ctx, K: int, local_prog=None):
    """Shared engage/reject decision for the overlapped shard_pallas
    exchange schedule (the core/shell split of the fused K-group).

    Returns ``(engage, core, shells, reasons)`` where ``core`` is the
    region dict ``{dim: (lo, hi)}`` for the core chunk, ``shells`` is a
    list of ``(dim, lo, hi)`` face slabs, and ``reasons`` carries the
    structured engage/reject codes the explain pass surfaces.  Pure
    geometry, never raises for infeasibility (``_prep_shard_pallas``
    raises only when the setting forces ``"on"``); the static checker's
    OVERLAP rule calls this same function so the two can never drift.

    Eligibility: setting not ``"off"``, at least one mesh-decomposed
    leading dim with a nonzero fused ghost width ``hK = radius×K``, the
    minor (lane) dim unsharded (lane-axis windows cannot restrict), and
    per sharded dim an aligned core span — ``lo = align_up(hK)``,
    ``hi = align_down(lsize − hK)`` with the sublane tile as the unit
    when the dim is some var's sublane axis (output DMA offsets must
    stay 8-aligned on real Mosaic) — of at least one alignment unit.
    The auto gate therefore engages exactly when every sharded dim's
    rank domain admits a core shrunk by ≥ hK per face (≈ 2·hK total).
    """
    opts = ctx._opts
    ana = ctx._ana
    dims = ana.domain_dims
    minor = dims[-1]
    nr = {d: opts.num_ranks[d] for d in dims}
    lsizes = opts.rank_domain_sizes
    # the core/shell shrink margin comes off THE TilePlan (the single
    # margin-math source for the fused pallas path); the minor (lane)
    # dim is never a tiled lead dim, so its ghost width stays the raw
    # fused halo for the extra-pad map below
    from yask_tpu.ops.tile_planner import TilePlan
    tplan = TilePlan(ctx._program, K)
    rad = ana.fused_step_radius()
    hK = {d: tplan.halo(d) for d in tplan.lead}
    hK[tplan.minor] = rad.get(tplan.minor, 0) * K
    setting = getattr(opts, "overlap_exchange", "auto")
    reasons: List[dict] = []

    if setting == "off":
        reasons.append({"code": "overlap_disabled",
                        "cause": "overlap_exchange=off"})
        return False, None, None, reasons
    if K < 2:
        # a K=1 group is one fused step: there is no core compute
        # window left to hide the exchange under, so the split buys
        # nothing (and single-step groups run whole on post-exchange
        # state inside the overlapped schedule — see ov_group)
        reasons.append({
            "code": ("overlap_infeasible" if setting == "on"
                     else "overlap_ineligible"),
            "cause": "wf_steps=1: a single-step group leaves no core "
                     "compute to overlap the exchange with"})
        return False, None, None, reasons
    if nr.get(minor, 1) > 1:
        reasons.append({"code": "overlap_ineligible",
                        "cause": f"minor dim '{minor}' is sharded "
                                 "(lane-axis windows cannot restrict)"})
        return False, None, None, reasons
    sharded = [d for d in dims[:-1] if nr.get(d, 1) > 1 and hK[d] > 0]
    if not sharded:
        reasons.append({"code": "overlap_ineligible",
                        "cause": "no sharded leading dim with a "
                                 "nonzero fused ghost width"})
        return False, None, None, reasons

    if local_prog is None:
        local_prog = ctx._csol.plan(
            lsizes, global_sizes=opts.global_domain_sizes,
            extra_pad={d: (hK[d], hK[d]) for d in dims})
    # Dims that are some var's sublane axis: split boundaries there
    # must ride the sublane tile (same rule build_pallas_chunk enforces
    # statically for its output DMA windows).
    from yask_tpu.compiler.lowering import tpu_tile_dims
    sub_t, _lane_t = tpu_tile_dims(local_prog.dtype)
    sub_dims = set()
    for g in local_prog.geoms.values():
        if g.is_scratch or len(g.axes) < 2:
            continue
        dn, kind = g.axes[-2]
        if kind == "domain" and dn != minor:
            sub_dims.add(dn)

    core: Dict[str, Tuple[int, int]] = {}
    shells: List[Tuple[str, int, int]] = []
    for d in sharded:
        q = sub_t if d in sub_dims else 1
        lo = -(-hK[d] // q) * q
        hi = ((lsizes[d] - hK[d]) // q) * q
        if hi - lo < q:
            reasons.append({
                "code": ("overlap_infeasible" if setting == "on"
                         else "overlap_ineligible"),
                "cause": f"dim '{d}': aligned core span [{lo},{hi}) is "
                         f"empty — rank domain {lsizes[d]} cannot "
                         f"cover 2×hK={2 * hK[d]} plus alignment "
                         f"(unit {q})", "dim": d})
            return False, None, None, reasons
        core[d] = (lo, hi)
        shells.append((d, 0, lo))
        shells.append((d, hi, lsizes[d]))
    reasons.append({"code": "overlap_engaged",
                    "core": {d: list(core[d]) for d in sorted(core)},
                    "hK": {d: hK[d] for d in sorted(core)}})
    return True, core, shells, reasons


def overlap_axes(sharded, engage, core, reasons) -> Dict[str, dict]:
    """The decision of :func:`overlap_decision` (and of what followed
    it in ``_prep_shard_pallas``) told a sharded mesh axis: ``{"taken":
    True, "core": [lo, hi)}`` where the core/shell split runs, else
    ``{"taken": False, "why": ...}`` with the cause.  The core may read
    no ghost that is still in flight, so the split is taken in every
    sharded dim or in none: an axis that could split and does not says
    which axis stood in its way."""
    if engage:
        return {d: {"taken": True, "core": list(core[d])}
                for d in sharded if d in core}
    last = reasons[-1] if reasons else {}
    why = last.get("cause", last.get("code", "not engaged"))
    at = last.get("dim")
    return {d: {"taken": False,
                "why": why if at in (None, d) else
                f"dim '{at}' has no core, and the split needs every "
                f"sharded dim ({why})"} for d in sharded}


def _make_overlap_step(prog, nr, lsizes, plan=None):
    """Interior/exterior-split step: the reference's compute/communication
    overlap (``run_solution`` exterior-then-interior structure,
    ``context.cpp:377-478``, ``MpiSection`` flags ``context.hpp:789-833``)
    recast for XLA's scheduler.

    Per stage: the *core* region (interior shrunk by the stage's ghost
    widths in sharded dims) is evaluated against the **pre-exchange**
    arrays — its data dependencies exclude the ppermutes, so XLA is free
    to run the collectives concurrently with core compute. The boundary
    *shell* slabs are then evaluated against the exchanged arrays.
    Overlapping shell corners recompute identical values (idempotent).
    """
    ana = prog.ana
    dims = ana.domain_dims
    stage_writes = []
    for stage in ana.stages:
        ws = []
        for part in stage.parts:
            if not part.is_scratch:
                for eq in part.eqs:
                    if eq.lhs.var_name() not in ws:
                        ws.append(eq.lhs.var_name())
        stage_writes.append(ws)

    def one_step(st, t):
        computed: Dict[str, object] = {}
        computed_post: Dict[str, object] = {}
        state_post = dict(st)
        # widths already exchanged per buffer — a later stage reading the
        # same var with *wider* ghosts must re-exchange the union, not
        # reuse the narrow refresh
        ring_w: Dict[str, Dict[str, Tuple[int, int]]] = {}
        post_w: Dict[str, Dict[str, Tuple[int, int]]] = {}

        for si in range(len(ana.stages)):
            reads = prog.stage_reads[si]
            split = prog.stage_reads_split[si]
            # refresh ghosts (post versions) for this stage's inputs —
            # BOTH buffers a read can hit: the computed (this-step)
            # array of an earlier stage, and the newest ring slot for
            # previous-step reads (a var can need both; refreshing only
            # computed would rotate stale ghosts into the next step)
            # ... batched through exchange_many so a coalescing
            # CommPlan packs this stage's refreshes into one ppermute
            # per (axis, direction)
            items, tags = [], []
            for vname, widths in split["computed"].items():
                g = prog.geoms[vname]
                if not any(nr.get(d, 1) > 1 for d in widths):
                    continue
                if vname in computed:
                    union, grew = _widen(post_w, vname, widths)
                    if vname not in computed_post or grew:
                        items.append((computed[vname], g, union))
                        tags.append(("c", vname, union))
            for vname, widths in split["ring"].items():
                g = prog.geoms[vname]
                if not any(nr.get(d, 1) > 1 for d in widths):
                    continue
                if g.is_written and g.has_step:
                    union, grew = _widen(ring_w, vname, widths)
                    if grew:
                        items.append((state_post[vname][-1], g, union))
                        tags.append(("s", vname, union))
            if items:
                new = exchange_many(items, nr, lsizes, plan)
                for (kind, vname, union), a in zip(tags, new):
                    if kind == "c":
                        computed_post[vname] = a
                        post_w[vname] = union
                    else:
                        ring = list(state_post[vname])
                        ring[-1] = a
                        state_post[vname] = ring
                        ring_w[vname] = union

            # stage ghost widths in sharded dims
            act: Dict[str, Tuple[int, int]] = {}
            for vname, widths in reads.items():
                for d, (l, r) in widths.items():
                    if nr.get(d, 1) > 1:
                        cl, cr = act.get(d, (0, 0))
                        act[d] = (max(cl, l), max(cr, r))
            splittable = act and all(
                lsizes[d] - l - r > 0 for d, (l, r) in act.items())

            post_env = {**computed, **computed_post}
            if not splittable:
                tmp = dict(post_env)
                prog.eval_stage(si, t, state_post, tmp, {})
                for name in stage_writes[si]:
                    computed[name] = tmp[name]
                    # an exchanged snapshot of an older value is now stale
                    computed_post.pop(name, None)
                    post_w.pop(name, None)
                continue

            # core with PRE-exchange arrays
            core = {d: (act.get(d, (0, 0))[0],
                        lsizes[d] - act.get(d, (0, 0))[1]) for d in dims}
            tmp_core = dict(computed)
            prog.eval_stage(si, t, st, tmp_core, {}, over=core)

            # shells with POST-exchange arrays, accumulating on core output
            tmp = dict(post_env)
            for name in stage_writes[si]:
                tmp[name] = tmp_core[name]
            interior = {d: (0, lsizes[d]) for d in dims}
            for d, (l, r) in act.items():
                for a, b in ((0, l), (lsizes[d] - r, lsizes[d])):
                    if b <= a:
                        continue
                    over = dict(interior)
                    over[d] = (a, b)
                    prog.eval_stage(si, t, state_post, tmp, {}, over=over)
            for name in stage_writes[si]:
                computed[name] = tmp[name]
                computed_post.pop(name, None)
                post_w.pop(name, None)

        # ring rotation (mirrors StepProgram.step), carrying exchanged rings
        new_state: Dict[str, List] = {}
        for name, ring in state_post.items():
            g = prog.geoms[name]
            if name in computed:
                if g.has_step:
                    new_state[name] = list(ring[1:]) + [computed[name]]
                else:
                    new_state[name] = [computed[name]]
            else:
                new_state[name] = list(ring)
        return new_state

    return one_step


def _make_specs_for(local_prog, nr):
    """PartitionSpec builder: domain axes with >1 rank follow the mesh."""
    from jax.sharding import PartitionSpec

    def specs_for(name):
        g = local_prog.geoms[name]
        spec = []
        for dn, kind in g.axes:
            spec.append(dn if (kind == "domain" and nr.get(dn, 1) > 1)
                        else None)
        return PartitionSpec(*spec)
    return specs_for


def alloc_resident(ctx):
    """Zero state of a shard mode as prepared: the sharded INTERIOR
    blocks (pads stripped -- the form the public fills write and every
    reader outside a launch reads), allocated directly under their
    NamedShardings so no device ever holds a global array.  The first
    launch pads them (:func:`rest_padded`); from then on the state
    rests padded (``RunState.padded``) until something reads it."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    gprog = ctx._program
    gsizes = ctx._opts.global_domain_sizes
    nr = {d: ctx._opts.num_ranks[d] for d in ctx._ana.domain_dims}
    names, specs_for = _prep_names_specs(ctx, nr)
    out = {}
    for k in names:
        g = gprog.geoms[k]
        shape = tuple(gsizes[dn] if kind == "domain" else ext
                      for (dn, kind), ext in zip(g.axes, g.shape))
        sh = NamedSharding(ctx._mesh, specs_for(k))
        out[k] = [jnp.zeros(shape, gprog.dtype, device=sh)
                  for _ in range(g.num_slots)]
    return out


# ---- the resting form of a shard state, and the one seam that converts --

class RestGeom:
    """The padded per-shard form a shard program computes on, takes and
    hands back: for every array of ``names`` the pads of each axis
    (ghost rows, physical-boundary zeros, Mosaic's alignment), the
    window that cuts the interior out again, its ``PartitionSpec`` and
    its shapes -- one shard's padded, and the global arrays' of both
    forms (ranks x the local extent in a split dim).  ``local_prog`` is
    the per-shard plan the program was built on: its pads follow the
    fused ghost width (K) and the rank grid, not the block, and ``key``
    says whether two programs compute on the same form: a state that
    rests in one's padded shards is the other's argument as it lies."""

    def __init__(self, ctx, local_prog, names, specs_for):
        lsizes = ctx._opts.rank_domain_sizes
        nr = ctx._opts.num_ranks
        self.mesh = ctx._mesh
        self.dtype = local_prog.dtype
        self.names = list(names)
        self.specs, self.pads, self.cuts, self.slots = {}, {}, {}, {}
        self.local, self.padded, self.interior = {}, {}, {}
        for k in self.names:
            g = local_prog.geoms[k]
            pads, cut, padded, interior = [], [], [], []
            for (dn, kind), n in zip(g.axes, g.shape):
                if kind == "domain":
                    pads.append(tuple(g.pads[dn]))
                    cut.append(slice(g.origin[dn],
                                     g.origin[dn] + lsizes[dn]))
                    padded.append(n * nr[dn])
                    interior.append(lsizes[dn] * nr[dn])
                else:
                    pads.append((0, 0))
                    cut.append(slice(None))
                    padded.append(n)
                    interior.append(n)
            self.specs[k] = specs_for(k)
            self.slots[k] = g.num_slots
            self.local[k] = tuple(g.shape)
            self.pads[k], self.cuts[k] = tuple(pads), tuple(cut)
            self.padded[k], self.interior[k] = (tuple(padded),
                                                tuple(interior))
        self.key = (tuple(self.mesh.shape.items()),
                    tuple((k, self.slots[k], self.local[k], self.pads[k])
                          for k in self.names))

    def avals(self, padded: bool = True):
        """The state's shapes under their shardings, to lower from."""
        import jax
        from jax.sharding import NamedSharding
        shapes = self.padded if padded else self.interior
        return {k: [jax.ShapeDtypeStruct(
            shapes[k], self.dtype,
            sharding=NamedSharding(self.mesh, self.specs[k]))]
            * self.slots[k] for k in self.names}


def _conversion(ctx, geom: RestGeom, k: str, strip: bool):
    """The compiled program that pads one interior array of var ``k``
    to ``geom``'s shards (``yt_shard_pad``) or cuts one back out
    (``yt_shard_strip``); None where the var has no pads.  THE pad and
    THE strip of a shard state: a ``shard_map`` program of one array,
    built once a signature (arrays of one shape share it) through
    ``aot_compile`` into ``ctx._jit_cache``, its one instruction under
    the named scope a device trace finds it by."""
    pads, cut, spec = geom.pads[k], geom.cuts[k], geom.specs[k]
    if not any(lr != (0, 0) for lr in pads):
        return None
    kind = "shard_strip" if strip else "shard_pad"
    key = (kind, spec, geom.local[k], pads)
    if key not in ctx._jit_cache:
        import jax
        import jax.numpy as jnp

        def yt_shard_pad(a):                # names the module
            with jax.named_scope(SCOPE_PAD):
                return jnp.pad(a, pads)

        def yt_shard_strip(a):              # names the module
            with jax.named_scope(SCOPE_STRIP):
                return a[cut]

        mapped = jax.shard_map(yt_shard_strip if strip else yt_shard_pad,
                               mesh=geom.mesh, in_specs=spec,
                               out_specs=spec, check_vma=False)
        with ctx._compile_span(kind, var=k):
            res = aot_compile(mapped, (geom.avals(padded=strip)[k][0],),
                              donate_argnums=0)
        ctx._compile_secs += res.compile_secs
        ctx._jit_cache[key] = res.fn
    return ctx._jit_cache[key]


def _convert_shards(ctx, geom: RestGeom, src: Dict, strip: bool) -> Dict:
    """Every array of ``src`` through its conversion, a var at a time:
    ``src`` gives each ring up as it goes (it is EMPTY afterwards) and
    the device is waited for behind every var, so that what a chip
    holds beside the larger form is one var of the other and not all of
    it.  The programs are built before the first array moves: a build
    that fails leaves ``src`` whole.  One ``run.repad`` span and one
    count (``run.state_strips`` / ``run.state_pads``) a conversion."""
    import jax
    fns = {k: _conversion(ctx, geom, k, strip) for k in geom.names}
    out = {}
    with span("run.repad", phase="dma", strip=strip):
        for k in geom.names:
            ring, fn = src.pop(k), fns[k]
            out[k] = ring if fn is None else jax.block_until_ready(
                [fn(ring.pop(0)) for _ in range(len(ring))])
    get_registry().counter(
        "run.state_strips" if strip else "run.state_pads").inc()
    return out


def pad_shards(ctx, geom: RestGeom, interior: Dict) -> Dict:
    """Sharded interiors -> ``geom``'s padded shards (ghost rows and
    physical-boundary pads zero).  Consumes ``interior``."""
    return _convert_shards(ctx, geom, interior, strip=False)


def strip_shards(ctx, geom: RestGeom, padded: Dict) -> Dict:
    """``geom``'s padded shards -> sharded interiors; whatever the pads
    held is dropped.  Consumes ``padded``."""
    return _convert_shards(ctx, geom, padded, strip=True)


def strip_rest(ctx) -> None:
    """The padded shards at rest become the resident interiors
    (``StencilContext._resident`` asks, whenever anything but the next
    launch reads the state).  A strip that fails on the way has given
    up part of the padded form: the state is lost, as it is to a
    launch that fails after donation."""
    rs = ctx._run
    padded, geom = rs.padded, rs.padded_geom
    rs.padded = rs.padded_geom = None
    rs.resident = strip_shards(ctx, geom, padded)


def rest_padded(ctx, geom: RestGeom) -> str:
    """Bring the resting state into ``geom``'s padded shards and say
    how a launch found it: ``"padded"`` where it lay so already (the
    last launch's program computes on the same form), else
    ``"interior"`` -- padded here from the resident interiors (a first
    call; a host access, another K or another rank grid since the last
    one) or from the global padded state (``_strip_global_interiors``).
    The conversions' programs are built before the old form is let go
    of."""
    rs = ctx._run
    if rs.padded is not None and rs.padded_geom.key == geom.key:
        return "padded"
    if rs.padded is None and rs.resident is None and rs.state is None:
        ctx._materialize_state()    # says how the state was lost
    # the strips with the pads, here inside the call: a reader between
    # two calls then finds its program built (no compile outside a
    # ``run.call``, none in a served request's snapshot)
    for k in geom.names:
        for strip in (False, True):
            _conversion(ctx, geom, k, strip)
    interior = _strip_global_interiors(ctx)
    rs.state = rs.resident = None       # given up array by array below
    rs.padded, rs.padded_geom = pad_shards(ctx, geom, interior), geom
    return "interior"


def _strip_global_interiors(ctx):
    """The state as sharded interior blocks: the resident ones where
    the state rests sharded (``ctx._resident``, which first strips the
    padded shards a shard program left), else cut from the global
    padded state.  Pads are identically zero (framework invariant), so
    stripping and re-attaching are pure device ops — no host round
    trip.

    Repeated shard-mode runs come here only when something read the
    state between them: a launch takes the padded shards the last one
    left as they lie (:func:`rest_padded`; VERDICT r1 item 9, one step
    further: neither a strip nor a pad a call).  Nothing is cleared
    here: the caller lets go of the old form once the new one's
    programs are built."""
    import jax
    from jax.sharding import NamedSharding
    if ctx._resident is not None:
        return ctx._resident
    gprog, mesh = ctx._program, ctx._mesh
    gsizes = ctx._opts.global_domain_sizes
    names, specs_for = _prep_names_specs(
        ctx, {d: ctx._opts.num_ranks[d] for d in ctx._ana.domain_dims})
    interior = {}
    with span("run.repad", phase="dma", strip=True):
        for k in names:
            g = gprog.geoms[k]
            idxs = []
            for dn, kind in g.axes:
                if kind == "domain":
                    idxs.append(slice(g.origin[dn],
                                      g.origin[dn] + gsizes[dn]))
                else:
                    idxs.append(slice(None))
            sh = NamedSharding(mesh, specs_for(k))
            interior[k] = [jax.device_put(a[tuple(idxs)], sh)
                           for a in ctx._state[k]]
    return interior


def _repad_global(gprog, names, out):
    """Re-attach the (zero) global pads on device."""
    import jax.numpy as jnp
    new_state = {}
    with span("run.repad", phase="dma", strip=False):
        for k in names:
            g = gprog.geoms[k]
            pads = []
            for dn, kind in g.axes:
                pads.append(g.pads[dn] if kind == "domain" else (0, 0))
            ring = []
            for res in out[k]:
                ring.append(jnp.pad(res, pads) if pads else res)
            new_state[k] = ring
    return new_state


def carry_period(program, names, K: int) -> int:
    """Groups of ``K`` fused steps after which every array a K-group
    loop carries is back in the position it came in by, written there
    by a launch that no longer read what the position held: what one
    scan iteration runs so that its carry copies nothing.  A group is
    out of place (it reads a slot's margins while it writes the new
    level), so its outputs are temporaries and the NEXT group's can
    land in the buffers the carry came in by: two.  A ring of ``L``
    slots of which a group renews ``min(K, L)`` is back in place after
    ``L / gcd(L, min(K, L))`` groups (``[s0, s1] -> [s1, new]``: two);
    the least common multiple of all of them, or two where that is
    past four groups (a program that long is not worth what its odd
    copies cost)."""
    period = 2
    for k in names:
        g = program.geoms[k]
        if g.is_written:
            renewed = min(K, g.num_slots)
            period = math.lcm(
                period, g.num_slots // math.gcd(g.num_slots, renewed))
    return period if period <= 4 else 2


def _slabs_read(items, locs, reads) -> Dict[Tuple[str, bool], Tuple[int, int]]:
    """Of the slabs one exchange round cuts from ``items`` (``(array,
    geom, widths)``, each the ``(var, slot)`` of ``locs``), those whose
    ghost rows some equation reads: ``{(dim, up): (slabs, bytes)}`` as
    ``_TraceStats.by_axis`` counts the round's sends.  A slab towards
    the higher rank fills the receiver's LEFT ghost rows, and is read
    where ``reads`` (``SolutionAnalysis.ghost_reads``) has the var
    reading to its left in that dim; a slab is counted whole or not at
    all, whatever its width beside the read's."""
    out: Dict[Tuple[str, bool], Tuple[int, int]] = {}
    for (k, _si), (a, g, widths) in zip(locs, items):
        for d, lr in widths.items():
            if d not in g.domain_dims:
                continue
            rows = a.shape[g.axis_of(d)]
            for up, width, asked in zip((True, False), lr,
                                        reads.get(k, {}).get(d, (0, 0))):
                if width > 0 and asked > 0:
                    n, b = out.get((d, up), (0, 0))
                    out[d, up] = (n + 1, b + a.size // rows * width
                                  * a.dtype.itemsize)
    return out


def _launch_attrs(ctx, halo: int, sent: Dict, rounds: int,
                  loop: Optional[Dict] = None,
                  read: Optional[Dict] = None) -> Dict:
    """What a shard program's ``run.launch`` span says beside ``k``:
    ``stages`` a step, the ghost width ``halo`` a round refreshes in a
    sharded dim, the rank grid ``mesh`` (``"2x2x1"``), and what is sent
    in the whole launch -- ``xrounds`` exchange rounds (the up-front
    refresh of every slot counted as one), ``xslabs`` edge slabs,
    ``xbytes`` their bytes as sent, pads included.  ``sent`` holds what
    ``_trace_stats`` counted, by axis and direction, while the program
    was traced: under
    ``"first"`` the up-front refresh, under ``"each"`` one of the
    ``rounds`` later rounds (a kind the program never traced sent
    nothing).  So the numbers follow the schedule that compiled,
    whatever it skips or coalesces.

    Two laws.  ``xslabs`` / ``xbytes`` are every slab the one SPMD
    trace cuts: both directions of every sharded axis, which is what a
    shard INTERIOR to every axis sends.  ``xslabs_<axis>`` /
    ``xbytes_<axis>``, one pair a sharded mesh axis, are what the
    BUSIEST SHARD THAT EXISTS sends across that axis: both directions
    where the axis is three or more wide, ONE (the larger) where it is
    two wide and every shard has one neighbour in it.  So in a 2-wide
    axis the old count is twice any chip's (at 2x2,
    ``xbytes == 2 * (xbytes_x + xbytes_y)``), and at x/4 the two agree
    (``xbytes == xbytes_x``).

    ``read`` (``_prep_shard_pallas``; ``run_shard_map``'s launches have
    none) holds, under the same two kinds, what of ``sent`` some
    equation of the step reads (``_slabs_read``): ``xslabs_read`` /
    ``xbytes_read`` and their per-axis pairs follow the two laws above,
    and ``xbytes_read == xbytes`` says that nothing is sent that no
    read asked for.

    ``loop`` (``_prep_shard_pallas``; ``run_shard_map``'s launches have
    none) says how the K-group loop runs: ``loop_groups`` groups a scan
    iteration (``carry_period``), ``loop_iters`` scan iterations,
    ``peeled_before`` / ``peeled_after`` the groups that run outside the
    scan, ahead of it (the overlapped schedule's group 0) and behind it
    (the scan's odd groups and the call's last), and ``reused`` the
    outputs a group writes onto the ring slot it evicts by explicit
    aliasing (``build_pallas_chunk(reuse_evicted=)``; 0 where the
    compiler needed no help).

    Beside these, which are the program's, every launch says how it
    found the state (``_launch_and_wait``): ``rest`` is ``"padded"``
    where it took the padded shards the last launch left as they lay,
    ``"interior"`` where :func:`rest_padded` padded for it first."""
    first = sent.get("first", {})
    each = sent.get("each", {})
    dims = ctx._ana.domain_dims
    nr = ctx._opts.num_ranks
    counts = [("", sent)] + ([("_read", read)] if read is not None else [])

    def way(kinds, d, up):
        """(slabs, bytes) along ``d`` in one direction."""
        f = kinds.get("first", {}).get((d, up), (0, 0))
        e = kinds.get("each", {}).get((d, up), (0, 0))
        return f[0] + rounds * e[0], f[1] + rounds * e[1]

    out = {"stages": len(ctx._ana.stages), "halo": int(halo),
           "mesh": "x".join(str(nr[d]) for d in dims),
           "xrounds": (1 if first else 0) + (rounds if each else 0)}
    for tag, _kinds in counts:
        out[f"xslabs{tag}"] = out[f"xbytes{tag}"] = 0
    for d in dims:
        if nr[d] < 2:
            continue
        for tag, kinds in counts:
            ways = [way(kinds, d, True), way(kinds, d, False)]
            both = tuple(map(sum, zip(*ways)))
            out[f"xslabs{tag}"] += both[0]
            out[f"xbytes{tag}"] += both[1]
            out[f"xslabs{tag}_{d}"], out[f"xbytes{tag}_{d}"] = (
                both if nr[d] > 2 else max(ways, key=lambda w: w[1]))
    out.update(loop or {})
    return out


def _launch_and_wait(ctx, key, fn, start: int, n: int, rest: str):
    """Enqueue the shard program of ``key`` on the padded shards at
    rest and wait for it; what it hands back rests in their place.
    The state is let go of immediately before the (buffer-donating)
    call: a failure before this point kept it valid, one inside the
    call loses it (``_materialize_state``: "solution state was lost").
    The launch span carries the attrs computed when ``key`` was built
    (``_launch_attrs``) and ``rest`` (:func:`rest_padded`); the
    exchange totals also accumulate in the process registry
    (``run.exchange_slabs`` / ``run.exchange_bytes``).  Both are timed
    into the call's record (``RunState.call``)."""
    import jax
    import jax.numpy as jnp
    attrs = ctx._launch_attrs.get(key, {})
    rs = ctx._run
    rec = rs.call
    padded, geom = rs.padded, rs.padded_geom
    rs.padded = rs.padded_geom = None
    with span("run.launch", phase="compute", k=n, rest=rest, **attrs):
        t0 = rec.clock()
        out = fn(padded, jnp.asarray(start, dtype=jnp.int32))
        rec.launch(n, rec.clock() - t0)
    del padded
    with span("run.wait", phase="compute"):
        t0 = rec.clock()
        jax.block_until_ready(out)
        rec.wait_secs += rec.clock() - t0
    if attrs.get("xslabs"):
        reg = get_registry()
        reg.counter("run.exchange_slabs").inc(attrs["xslabs"])
        reg.counter("run.exchange_bytes").inc(attrs["xbytes"])
    rs.padded, rs.padded_geom = out, geom


def _launch_from_rest(ctx, key, start: int, n: int) -> None:
    """How both shard modes end a call once the program of ``key`` is
    built: the state is brought to rest as that program's padded shards
    (:func:`rest_padded`: as the last launch left them, or padded per
    shard from the interiors on a first call or after a host access,
    themselves cut from the global padded state where that is what is
    held; pads are identically zero, so these are pure device ops), the
    program runs on them, and the run timer takes the conversion, where
    one happened, and the program, less what of it was compilation (the
    conversions' own builds)."""
    t0r = time.perf_counter()
    cs0 = ctx._compile_secs
    rest = rest_padded(ctx, ctx._shard_rest[key])
    t0r += ctx._compile_secs - cs0
    _launch_and_wait(ctx, key, ctx._jit_cache[key], start, n, rest)
    ctx._run_timer._elapsed += time.perf_counter() - t0r


def run_shard_map(ctx, start: int, n: int) -> None:
    """Advance ``n`` steps in explicit shard_map mode, on the padded
    shards the state rests in (``RunState.padded``; padded first where
    it rests otherwise, :func:`rest_padded`), which the program takes
    and hands back."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec

    opts = ctx._opts
    ana = ctx._ana
    mesh = ctx._mesh
    nr = {d: opts.num_ranks[d] for d in ana.domain_dims}
    gsizes = opts.global_domain_sizes
    lsizes = opts.rank_domain_sizes
    dirn = ana.step_dir

    # Static local geometry (pads = halos); the traced twin inside the body
    # only differs in rank offsets.
    # XLA-only per-shard geometry: no Mosaic alignment (see VarGeom)
    local_prog = ctx._csol.plan(lsizes, global_sizes=gsizes,
                                mosaic_align=False)
    gprog = ctx._program

    names = [k for k, g in gprog.geoms.items() if not g.is_scratch]
    slots = {k: gprog.geoms[k].num_slots for k in names}
    specs_for = _make_specs_for(local_prog, nr)

    # The CommPlan (axis order + coalescing) is baked into the traced
    # exchange bodies, so it joins overlap_comms in the cache key —
    # toggling either between equal-length runs must never reuse the
    # other schedule's compiled body.
    plan = ctx.comm_plan()
    if plan.errors:
        raise YaskException("communication plan invalid: "
                            + "; ".join(plan.errors))
    key = ("shard_map", n, opts.overlap_comms) + plan.key()
    sent: Dict[str, dict] = {}   # see _launch_attrs

    def build():
        in_specs = ({k: [specs_for(k)] * slots[k] for k in names},
                    PartitionSpec())
        out_specs = {k: [specs_for(k)] * slots[k] for k in names}

        def yt_shard_map(padded_state, t0):   # names the module
            # Per-shard program with traced rank offsets.
            offs = {d: lax.axis_index(d) * lsizes[d] if nr[d] > 1 else 0
                    for d in ana.domain_dims}
            prog = ctx._csol.plan(lsizes, global_sizes=gsizes,
                                  rank_offset=offs, mosaic_align=False)

            # 1) the local blocks come padded (ghost + physical-boundary
            #    zeros: ``rest_padded``, or the last call's own).
            state = {k: list(padded_state[k]) for k in names}

            # 2) pre-exchange every slot once so older ring slots carry
            #    valid ghosts (steady-state invariant: only the newest slot
            #    is stale afterwards) — batched, so a coalescing CommPlan
            #    packs all slabs per (axis, direction) into one ppermute.
            items, locs = [], []
            for k in names:
                g = prog.geoms[k]
                widths = {d: g.var.halo.get(d, (0, 0))
                          for d in g.domain_dims}
                widths = {d: w for d, w in widths.items() if w != (0, 0)}
                if widths:
                    for si, a in enumerate(state[k]):
                        items.append((a, g, widths))
                        locs.append((k, si))
            mark = _trace_stats.mark()
            for (k, si), a in zip(locs,
                                  exchange_many(items, nr, lsizes, plan)):
                state[k][si] = a
            sent["first"] = _trace_stats.since(mark)

            # 3) scan steps; before each stage refresh stale ghosts only.
            def one_step_plain(st, t):
                # widths already applied per buffer: a later stage with
                # wider ghost reads re-exchanges the union
                applied = {}

                def hook(si, state_, computed):
                    # refresh BOTH buffers a stage's reads can hit (see
                    # stage_read_widths_split: refreshing only the
                    # computed array would leave previous-step ring
                    # reads of the same var with stale shard ghosts) —
                    # batched through exchange_many so the stage's
                    # refreshes share collectives under a coalescing
                    # CommPlan
                    split = prog.stage_reads_split[si]
                    items, tags = [], []
                    for vname, widths in split["computed"].items():
                        if vname not in computed:
                            continue
                        g2 = prog.geoms[vname]
                        u, grew = _widen(applied, (vname, "c"), widths)
                        if grew:
                            items.append((computed[vname], g2, u))
                            tags.append(("c", vname, u))
                    for vname, widths in split["ring"].items():
                        g2 = prog.geoms[vname]
                        if not (g2.is_written and g2.has_step):
                            continue
                        u, grew = _widen(applied, (vname, "s"), widths)
                        if grew:
                            items.append((state_[vname][-1], g2, u))
                            tags.append(("s", vname, u))
                    if items:
                        new = exchange_many(items, nr, lsizes, plan)
                        for (kind, vname, u), a in zip(tags, new):
                            if kind == "c":
                                computed = {**computed, vname: a}
                                applied[(vname, "c")] = u
                            else:
                                ring = list(state_[vname])
                                ring[-1] = a
                                state_ = {**state_, vname: ring}
                                applied[(vname, "s")] = u
                    return state_, computed

                return prog.step(st, t, halo_hook=hook)

            one_step_ov = _make_overlap_step(prog, nr, lsizes,
                                             plan=plan)
            one_step = one_step_ov if ctx._opts.overlap_comms \
                else one_step_plain

            def scan_body(carry, _):
                st, t = carry
                mark = _trace_stats.mark()
                st = one_step(st, t)
                sent["each"] = _trace_stats.since(mark)
                return (st, t + dirn), None

            (state, _), _ = lax.scan(scan_body, (state, t0), None, length=n)

            # 4) the padded blocks go back as they are: the next call's
            #    argument, or what ``strip_rest`` cuts the interiors from.
            return state

        mapped = jax.shard_map(yt_shard_map, mesh=mesh,
                               in_specs=in_specs, out_specs=out_specs,
                               check_vma=False)
        return jax.jit(mapped, donate_argnums=0)

    if key not in ctx._jit_cache:
        t0c = time.perf_counter()
        fn = ctx._jit_cache[key] = build()
        # the padded form the program takes and hands back
        geom = ctx._shard_rest[key] = RestGeom(ctx, local_prog, names,
                                               specs_for)
        # ``fn`` is jitted lazily: trace it here, on shapes alone, so
        # that its first launch's span already says what it exchanges
        jax.eval_shape(fn, geom.avals(),
                       jnp.asarray(start, dtype=jnp.int32))
        halo = max([w for k in names for d, lr in
                    local_prog.geoms[k].var.halo.items()
                    if nr.get(d, 1) > 1 for w in lr], default=0)
        ctx._launch_attrs[key] = _launch_attrs(ctx, halo, sent, n)
        ctx._compile_secs += time.perf_counter() - t0c
    _launch_from_rest(ctx, key, start, n)


def _prep_shard_pallas(ctx, n: int, K: int, blk):
    """Validate + plan one ``(n, K, blk)`` shard_pallas variant.

    Returns ``(names, specs_for, build)`` where ``build()`` is the
    un-jitted shard_map program. Raises
    ``YaskException`` for infeasible candidates (minor-dim sharding at
    K>1, rank domain smaller than the fused ghost width, tile over the
    VMEM budget) — the auto-tuner relies on this to skip them."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk

    opts = ctx._opts
    ana = ctx._ana
    mesh = ctx._mesh
    dims = ana.domain_dims
    minor = dims[-1]
    nr = {d: opts.num_ranks[d] for d in dims}
    gsizes = opts.global_domain_sizes
    lsizes = opts.rank_domain_sizes
    dirn = ana.step_dir

    if K > 1 and nr.get(minor, 1) > 1:
        raise YaskException(
            f"shard_pallas with wf_steps={K} > 1 cannot shard the minor "
            f"dim '{minor}' (its in-tile region never shrinks); use "
            "wf_steps 1 or keep the minor dim whole")
    # ghost widths off THE TilePlan (single margin-math source; the
    # minor dim keeps the raw fused halo — it is never a tiled lead dim)
    from yask_tpu.ops.tile_planner import TilePlan
    _tplan = TilePlan(ctx._program, K)
    rad = ana.fused_step_radius()
    hK = {d: _tplan.halo(d) for d in _tplan.lead}
    hK[_tplan.minor] = rad.get(_tplan.minor, 0) * K
    for d in dims:
        if nr.get(d, 1) > 1 and lsizes[d] < hK[d]:
            raise YaskException(
                f"rank domain {lsizes[d]} in dim '{d}' smaller than the "
                f"fused ghost width {hK[d]} (radius × wf_steps)")

    # Communication schedule for this (mode, K): axis order +
    # coalescing off the ICI/DCN cost model, baked into the traced
    # exchange closures below (the variant cache key carries the knobs)
    plan = ctx.comm_plan(K)
    if plan.errors:
        raise YaskException("communication plan invalid: "
                            + "; ".join(plan.errors))

    # Per-shard plan: pads grown to the fused ghost width so the kernel's
    # halo DMAs stay inside the array and exchanges have room.
    extra = {d: (hK[d], hK[d]) for d in dims}
    local_prog = ctx._csol.plan(lsizes, global_sizes=gsizes,
                                extra_pad=extra)

    names = [k for k, g in ctx._program.geoms.items() if not g.is_scratch]
    slots = {k: ctx._program.geoms[k].num_slots for k in names}
    specs_for = _make_specs_for(local_prog, nr)

    groups, rem = divmod(n, K)
    interp = ctx._env.get_platform() != "tpu"
    # per fused depth: the call's last, shorter group plans with the
    # budget of its own depth (as the single-device path does)
    budget = ctx.vmem_budget(K)
    budget_rem = ctx.vmem_budget(rem) if rem else budget
    # Temporal blocking across shards: the skewed wavefront may engage
    # inside each shard when the stream dim is NOT mesh-decomposed —
    # the carry then never crosses a shard boundary and the r·K ghost
    # pads cover the skew margins, so the distributed path stops paying
    # the uniform 2·r·K recompute margin in that dim (the rank-level
    # temporal-tiling analog of the reference's update_tb_info,
    # setup.cpp:863).
    lead_local = dims[:-1]
    # the carry must stay on-shard, so the stream dim may engage exactly
    # when it is not mesh-decomposed (the r·K ghost pads then cover its
    # skew margins)
    unsh = tuple(d for d in lead_local if nr.get(d, 1) == 1)
    skw = None if ctx._opts.skew_wavefront else False
    # the whole-shard chunks write a new level onto the ring slot it
    # evicts where the kernel never reads that slot (``reuse_evicted``):
    # the program drops the slot after the launch, and a ring that only
    # rotates then moves nothing through the loop's carry
    chunk, tile_bytes = build_pallas_chunk(
        local_prog, fuse_steps=K, block=blk, interpret=interp,
        distributed=True, vmem_budget=budget,
        vinstr_cap=ctx._opts.max_tile_vinstr, skew=skw,
        unsharded_dims=unsh, reuse_evicted=True)
    chunk_rem = None
    if rem:
        chunk_rem, _ = build_pallas_chunk(
            local_prog, fuse_steps=rem, block=blk, interpret=interp,
            distributed=True, vmem_budget=budget_rem,
            vinstr_cap=ctx._opts.max_tile_vinstr, skew=skw,
            unsharded_dims=unsh, reuse_evicted=True)
    ctx._env.trace_msg(
        f"shard_pallas chunk: K={K}, blocks={blk or 'planner'}, "
        f"tile {tile_bytes / 2**20:.2f} MiB, "
        f"skew={chunk.tiling['skew']}, "
        f"margin_overhead={chunk.tiling['margin_overhead']}")

    # ---- overlapped exchange schedule (core/shell split) ---------------
    # The core chunk covers the interior shrunk by hK per sharded face
    # and is evaluated against PRE-exchange state: its reads stay inside
    # [core_lo−hK, core_hi+hK) ⊆ the interior, so it carries no data
    # dependence on the ppermutes and XLA overlaps the previous group's
    # collectives with it.  The width-hK shell slabs then run on the
    # post-exchange state — the reference's exterior/interior MPI
    # overlap (context.cpp:377-478) at the fused-chunk level.
    ngroups = groups + (1 if rem else 0)
    ov_engage, ov_core, ov_shells, ov_reasons = \
        overlap_decision(ctx, K, local_prog=local_prog)
    ov_setting = getattr(opts, "overlap_exchange", "auto")
    if ov_setting == "on" and not ov_engage:
        raise YaskException(
            "overlap_exchange=on but the core/shell split is "
            "infeasible: " + "; ".join(
                r.get("cause", r["code"]) for r in ov_reasons))
    if ov_engage and ngroups < 2:
        ov_engage = False
        ov_reasons.append({"code": "overlap_inactive",
                           "cause": f"single K-group (n={n} ≤ K={K}): "
                                    "no exchange to overlap"})
    chunk_core = chunk_core_rem = None
    shell_chunks: List = []
    shell_chunks_rem: List = []
    if ov_engage:
        def _build_split(fs):
            fs_budget = budget if fs == K else budget_rem
            core_c, _ = build_pallas_chunk(
                local_prog, fuse_steps=fs, block=blk, interpret=interp,
                distributed=True, vmem_budget=fs_budget,
                vinstr_cap=ctx._opts.max_tile_vinstr, skew=skw,
                unsharded_dims=unsh, region=ov_core, arm="core")
            # a shell writes its slab into the core's output where it
            # belongs (``onto``): no full-size output a shell and no
            # merge copy
            sh_cs = []
            for d, a, b in ov_shells:
                sc, _ = build_pallas_chunk(
                    local_prog, fuse_steps=fs, block=blk,
                    interpret=interp, distributed=True,
                    vmem_budget=fs_budget,
                    vinstr_cap=ctx._opts.max_tile_vinstr, skew=skw,
                    unsharded_dims=unsh,
                    region={d: (a, b)}, arm="shell", onto=True)
                sh_cs.append(sc)
            return core_c, sh_cs
        try:
            chunk_core, shell_chunks = _build_split(K)
            if rem >= 2:
                chunk_core_rem, shell_chunks_rem = _build_split(rem)
            elif rem:
                # a 1-step remainder group has no core compute window:
                # ov_group runs the whole chunk_rem on post-exchange
                # state (core_fn None), keeping bit-equality with the
                # serial schedule
                ov_reasons.append({
                    "code": "overlap_rem_unsplit",
                    "cause": "remainder group fuses a single step: run "
                             "whole on post-exchange state (no compute "
                             "to hide its exchange under)"})
        except YaskException as e:
            # the split planner rejected a region (e.g. an unalignable
            # boundary): fall back to the serial schedule unless forced
            if ov_setting == "on":
                raise
            ov_engage = False
            chunk_core = chunk_core_rem = None
            ov_reasons.append({"code": "overlap_fallback",
                               "cause": str(e)})
        else:
            ctx._env.trace_msg(
                f"shard_pallas overlap: core="
                f"{ {d: list(v) for d, v in ov_core.items()} }, "
                f"{len(ov_shells)} shell slab(s)")
    chunk.tiling["overlap_exchange"] = bool(ov_engage)
    chunk.tiling["overlap_reasons"] = list(ov_reasons)
    chunk.tiling["overlap"] = overlap_axes(
        [d for d in dims if nr.get(d, 1) > 1], ov_engage, ov_core,
        ov_reasons)
    # every per-axis comm decision rides the tiling record (stats /
    # explain pass read it from here)
    chunk.tiling["comm"] = plan.record()
    if ov_engage:
        chunk.tiling["overlap_core"] = {d: list(v)
                                        for d, v in ov_core.items()}
        # what a launch's input and output DMAs move: the arms that
        # run, not the whole-shard chunk they stand in for
        for moved in ("fetch_bytes_per_step", "write_bytes_per_step"):
            chunk.tiling[moved] = sum(
                c.tiling[moved] for c in [chunk_core] + shell_chunks)

    # how the K-group loop runs (``carry_period``): the groups ahead of
    # the scan (the overlapped schedule's group 0), the scan's own, and
    # the ones behind it (the scan's odd groups, peeled by ``unroll``,
    # and the call's last, which no exchange follows)
    head = 1 if ov_engage else 0
    tail = 1 if rem or not ov_engage else 0
    nscan = ngroups - head - tail
    per = max(1, min(carry_period(local_prog, names, K), nscan))
    loop = {"loop_groups": per, "loop_iters": nscan // per,
            "peeled_before": head, "peeled_after": nscan % per + tail,
            "reused": len(chunk.tiling["reused"])}
    chunk.tiling["loop"] = loop   # what _launch_attrs says of it

    # The argument is donated and its buffers are the result's, ring
    # by ring (JAX pairs a donated input with the first output of its
    # shape), and a ring is back in its own buffers only where the
    # groups leave it there: a group that renews a ring out of place
    # (no slot of it written onto the one it evicts) alternates between
    # two sets of buffers, so after an odd number of groups the result
    # lies in the other set, and the compiler first copies the argument
    # aside, a whole-array copy a slot and call (read off the
    # described-v5e HLO of iso3dfd's five K=2 groups: ``copy`` of both
    # ``pressure`` slots ahead of group 0, 1.4 GiB each).  Such a ring
    # (``aside``) takes its result in new buffers: the argument's go to
    # an output put FIRST that lies in them anyway, the ring as the
    # up-front exchange left it, which the launch drops.
    reused = {slot.split("/")[0] for slot in chunk.tiling["reused"]}
    aside = sorted(
        k for k in names if ngroups % 2
        and local_prog.geoms[k].is_written and k not in reused)

    sent: Dict[str, dict] = {}   # see _launch_attrs
    read: Dict[str, dict] = {}   # of ``sent``, what an equation reads
    # a var's slab towards a side is as wide as the group reads that
    # var's ghost rows there, where the analysis can say it; else hK
    need = ana.group_ghost_widths(K)
    reads = ana.ghost_reads()

    def build():
        """The shard_map program, un-jitted."""
        in_specs = ({k: [specs_for(k)] * slots[k] for k in names},
                    PartitionSpec())
        out_specs = ({k: in_specs[0][k] for k in aside}, in_specs[0])

        def _widths(k, g):
            """``{dim: (left, right)}`` ghost rows of var ``k`` a round
            refreshes: what the group reads of them, side by side,
            where the analysis can say it (``need``; a side nothing
            reads has no entry and is not sent), else the cone."""
            if need is None:
                return {d: (hK[d], hK[d]) for d in g.domain_dims
                        if nr.get(d, 1) > 1 and hK[d] > 0}
            return {d: lr for d, lr in need.get(k, {}).items()
                    if nr.get(d, 1) > 1 and d in g.domain_dims
                    and max(lr) > 0}

        def _apply_many(state, items, locs, round_kind):
            """One exchange round over ``items``; an item whose widths
            are empty rides along untouched, so that ``exchange_many``
            sees every array a round could have refreshed."""
            if not any(w for _a, _g, w in items):
                return state
            rings = {}
            mark = _trace_stats.mark()
            for (k, si), a in zip(locs,
                                  exchange_many(items, nr, lsizes, plan)):
                rings.setdefault(k, list(state[k]))[si] = a
            sent[round_kind] = _trace_stats.since(mark)
            read[round_kind] = _slabs_read(items, locs, reads)
            return {**state, **rings}

        def exchange_all(state):
            """Full refresh: every slot of every var whose ghost rows
            the group reads (run once up front — read-only vars and
            surviving ring slots keep valid ghosts after this), batched
            so a coalescing CommPlan shares collectives across vars and
            slots."""
            items, locs = [], []
            for k in names:
                g = local_prog.geoms[k]
                widths = _widths(k, g)
                for si, a in enumerate(state[k]):
                    items.append((a, g, widths))
                    locs.append((k, si))
            return _apply_many(state, items, locs, "first")

        def exchange_newest(state):
            """Per-group refresh: only the min(K, alloc) slots the chunk
            just produced (it re-zeroed their pads); everything else
            still holds valid ghosts."""
            items, locs = [], []
            for k in names:
                g = local_prog.geoms[k]
                if not g.is_written:
                    continue
                widths = _widths(k, g)
                nback = min(K, len(state[k]))
                for si in range(len(state[k]) - nback, len(state[k])):
                    items.append((state[k][si], g, widths))
                    locs.append((k, si))
            return _apply_many(state, items, locs, "each")

        def yt_shard_pallas(padded_state, t0):   # names the module
            offs = {d: lax.axis_index(d) * lsizes[d] if nr[d] > 1 else 0
                    for d in dims}
            off_vec = jnp.stack(
                [jnp.asarray(offs[d], dtype=jnp.int32) for d in dims])

            # 1) the shards come padded (``rest_padded``: ghost rows
            #    and physical-boundary zeros), as the last call's last
            #    group left them or as the seam padded the interiors;
            #    ghost rows that face a neighbour hold whatever that
            #    left there until the exchange below rewrites them.
            state = {k: list(padded_state[k]) for k in names}

            # 2) one full exchange up front, then per K-group the fused
            #    chunk runs and only its freshly produced slots (whose
            #    pads it re-zeroed) are re-exchanged — read-only vars and
            #    surviving slots never move again. The final chunk is
            #    unrolled so no exchange is wasted after the last group.
            #    A scan iteration runs ``carry_period`` groups: what it
            #    hands on was written into buffers the loop already
            #    owns and no longer reads, so the carry copies nothing.
            #    The last group's padded shards are the program's
            #    result: the next call's argument as they lie.
            state = exchange_all(state)
            return {k: state[k] for k in aside}, groups_of(state, t0,
                                                           off_vec)

        def groups_of(state, t0, off_vec):
            """The call's K-groups on the freshly exchanged state."""
            if not ov_engage:
                def group(carry, _):
                    st, t = carry
                    st = chunk(st, t, off_vec)
                    st = exchange_newest(st)
                    return (st, t + K * dirn), None

                (state, t), _ = lax.scan(group, (state, t0), None,
                                         length=nscan, unroll=per)
                if rem:
                    return chunk_rem(state, t, off_vec)
                return chunk(state, t, off_vec)

            # Overlapped schedule: group 0 runs the plain chunk on the
            # fully exchanged state; each later group exchanges FIRST,
            # then evaluates the core against the pre-exchange state
            # (its reads stay ≥ hK from every sharded face, so the
            # ppermutes are not on its dataflow and XLA overlaps them)
            # and the shell slabs against the post-exchange state.
            # Same T−1 exchanges as the serial schedule, moved from the
            # group tails to the heads.
            def ov_group(st, t, core_fn, shell_fns, gk):
                st_post = exchange_newest(st)
                if core_fn is None:
                    # single-step group (K=1 remainder): one fused step
                    # leaves no core compute window to hide its exchange
                    # under, and the split would trade bit-equality with
                    # the serial schedule for nothing — run the whole
                    # chunk on the post-exchange state instead (same
                    # exchange placement, same values to the last bit).
                    fo = (chunk if gk == K else chunk_rem)(
                        st_post, t, off_vec)
                    out = {}
                    for k in names:
                        g = local_prog.geoms[k]
                        if not g.is_written:
                            out[k] = list(st_post[k])
                            continue
                        L = len(st_post[k])
                        nb = min(gk, L)
                        out[k] = (list(st_post[k][nb:])
                                  + list(fo[k][L - nb:]))
                    return out
                # the core's output, then each shell's slab written
                # into it (where two shells cross, at a corner of the
                # shard, both wrote the same values).  The surviving
                # (rotated-forward) slots come from st_post: they keep
                # their exchanged pads
                news = core_fn.written(st, t, off_vec)
                for fn in shell_fns:
                    news = fn.written(st_post, t, off_vec, news)
                return core_fn.merge(st_post, news)

            state = chunk(state, t0, off_vec)

            def group(carry, _):
                st, t = carry
                st = ov_group(st, t, chunk_core, shell_chunks, K)
                return (st, t + K * dirn), None

            (state, t), _ = lax.scan(
                group, (state, t0 + K * dirn), None,
                length=nscan, unroll=per)
            if rem:
                state = ov_group(state, t, chunk_core_rem,
                                 shell_chunks_rem, rem)
            return state

        return jax.shard_map(yt_shard_pallas, mesh=mesh,
                             in_specs=in_specs, out_specs=out_specs,
                             check_vma=False)

    # carried to get_shard_pallas_fn, which records it into
    # ctx._pallas_tiling only AFTER a successful Mosaic compile (a
    # failure must not leave stats modeling a tiling that never ran —
    # same invariant as the single-device path, context.py)
    build.tiling = chunk.tiling
    # likewise recorded after the compile, whose trace fills ``sent``:
    # one up-front refresh, then one round after every group but the last
    halo = max([hK[d] for d in dims if nr.get(d, 1) > 1], default=0)
    build.launch_attrs = lambda: _launch_attrs(ctx, halo, sent,
                                               ngroups - 1, loop, read)
    # the padded form the program takes and hands back
    build.rest = RestGeom(ctx, local_prog, names, specs_for)
    return names, specs_for, build


class _ShardLaunch:
    """A compiled ``yt_shard_pallas`` as its callers hold it: called on
    the padded state (donated), it returns the padded state and drops
    the program's first output, which only gives the buffers of the
    rings ``aside`` somewhere to go (``_prep_shard_pallas``).
    ``as_text`` and ``memory_analysis`` are the executable's
    (``StencilContext.compiled_texts`` / ``compiled_memory``)."""

    def __init__(self, build, like, start: int):
        """``build()`` compiled ahead from the shapes of ``like``: the
        padded state, or its avals."""
        import jax.numpy as jnp
        self.exe = aot_compile(
            build(), (like, jnp.asarray(start, dtype=jnp.int32)),
            donate_argnums=0).fn
        self.as_text = self.exe.as_text
        self.memory_analysis = self.exe.memory_analysis

    def __call__(self, state, t0):
        return self.exe(state, t0)[1]


def shard_pallas_key(ctx, n: int, K: int, blk) -> Tuple:
    """What one ``(n, K, blk)`` variant of the shard_pallas program is
    held under, under the settings as they stand: its executable in
    ``ctx._jit_cache``, its span attrs in ``ctx._launch_attrs``, the
    padded form it takes in ``ctx._shard_rest``."""
    return ("shard_pallas", n, K, blk) + ctx._pallas_variant_key()


def get_shard_pallas_fn(ctx, start: int, n: int, K: int, blk):
    """AOT-compiled shard_pallas program for ``(n, K, blk)``, cached in
    the context's jit cache — the single compile policy (donation, AOT
    lowering, compile-time accounting) for both tuner trials and
    production runs. Trials use ``n == K`` (one group per call) while
    production runs key on the full run span, so a tuned variant is
    re-lowered once for its first real run — the trade for the tuner
    timing exactly one exchange+group instead of a whole run.
    The program is lowered from the shapes of the padded shards it
    takes and hands back (``build.rest``, kept as
    ``ctx._shard_rest[key]``: the form a caller brings the state into,
    :func:`rest_padded`, :func:`pad_shards`). May raise
    ``YaskException`` for infeasible candidates."""
    var = ctx._pallas_variant_key()
    key = shard_pallas_key(ctx, n, K, blk)
    if key not in ctx._jit_cache:
        _, _, build = _prep_shard_pallas(ctx, n, K, blk)
        from yask_tpu.ops.pallas_stencil import plan_attrs
        t0c = time.perf_counter()
        # the twin of context.py's span: the whole-shard chunk's plan
        tiling = build.tiling
        with ctx._compile_span("shard_pallas", k=K, n=n,
                               **plan_attrs(tiling)):
            ctx._jit_cache[key] = _ShardLaunch(
                build, build.rest.avals(), start)
        secs = time.perf_counter() - t0c
        ctx._compile_secs += secs
        # only after a successful compile (see _prep_shard_pallas)
        ctx._pallas_tiling[("shard_pallas", K, blk) + var] = dict(
            tiling, compile_secs=secs, cache_hit=None)
        ctx._launch_attrs[key] = build.launch_attrs()
        ctx._shard_rest[key] = build.rest
    return ctx._jit_cache[key]


def _prep_names_specs(ctx, nr):
    """(names, specs_for) for an already-compiled variant (no re-plan:
    axes structure is K-independent, so the global program's geometry
    serves for the PartitionSpecs)."""
    gprog = ctx._program
    names = [k for k, g in gprog.geoms.items() if not g.is_scratch]
    return names, _make_specs_for(gprog, nr)


def run_shard_pallas(ctx, start: int, n: int) -> None:
    """Distributed fused stepping: shard_map outer + Pallas inner.

    The scaling path for the flagship multi-chip target (reference
    wave-front + MPI-exchange interplay, ``context.cpp:352-576``): each
    shard carries ghost pads sized radius×K, ``lax.ppermute`` refreshes
    them once per K-step group, and the fused Pallas chunk advances K
    steps entirely on-shard (its domain mask works in global coordinates
    via the shard offset, so exchanged ghosts update through sub-steps
    while physical boundaries stay zero).
    """
    opts = ctx._opts
    dims = ctx._ana.domain_dims

    K = min(max(opts.wf_steps, 1), n)
    bs = opts.block_sizes
    blk = None
    if any(bs[d] > 0 for d in dims[:-1]):
        blk = tuple(bs[d] if bs[d] > 0 else 8 for d in dims[:-1])
    key = shard_pallas_key(ctx, n, K, blk)

    if key not in ctx._jit_cache:
        # AOT-compile (shared policy: get_shard_pallas_fn) so the first
        # timed call doesn't include XLA/Mosaic compilation: the program
        # is built, from shapes alone, before the state is touched.
        get_shard_pallas_fn(ctx, start, n, K, blk)
    _launch_from_rest(ctx, key, start, n)
