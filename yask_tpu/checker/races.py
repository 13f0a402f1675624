"""Dependency/race pass: equation-level scans plus distributed proofs.

The analysis pipeline (``SolutionAnalysis``) RAISES on the races it
knows about, which is right for ``prepare_solution`` but useless for a
diagnostic tool — one bad equation would hide every other finding.
This pass re-runs the same rules non-raising, directly over
``soln.get_equations()`` (so it works on solutions whose ``analyze()``
would throw), sharing the single rule definitions where they exist
(``analysis.missing_dim_race``, ``Var.min_step_alloc_size``).

The distributed sub-pass turns the shard planner's runtime raises
(``_prep_shard_pallas``) and the ghost-pad coverage argument from the
round-5 distributed-skew work into static proofs: per mesh-decomposed
dim the rank domain must cover the fused ghost width radius×K, the
minor dim may not be sharded at K>1, and each engaged skew dim's
margins (K·r left, r+E_sk right) must fit inside the radius×K ghost
pads — which holds exactly when the profit gate engaged it.
"""

from __future__ import annotations

from yask_tpu.checker.diagnostics import CheckReport
from yask_tpu.compiler.analysis import missing_dim_race
from yask_tpu.compiler.expr import PointVisitor

PASS = "races"
PASS_DIST = "distributed"


def _reads_of(eq):
    pv = PointVisitor()
    eq.rhs.accept(pv)
    if eq.cond is not None:
        eq.cond.accept(pv)
    if eq.step_cond is not None:
        eq.step_cond.accept(pv)
    return pv.points


def check_races(report: CheckReport, ctx, ana_error=None) -> None:
    report.ran(PASS)
    soln = ctx._csol.soln if ctx._csol is not None else ctx._soln
    eqs = soln.get_equations()
    domain_dims = soln.domain_dim_names()

    # writers per var this step (non-scratch), for WAW + same-point
    writers = {}
    step_dir = 0
    for eq in eqs:
        writers.setdefault(eq.lhs.var_name(), []).append(eq)
        so = eq.lhs.step_offset()
        if so in (1, -1) and step_dir == 0:
            step_dir = so
    if step_dir == 0:
        step_dir = 1

    for eq in eqs:
        var = eq.lhs.get_var()
        # RACE-MISSING-DIM: the single shared rule definition.
        varying = missing_dim_race(eq, domain_dims)
        if varying:
            report.add(
                "RACE-MISSING-DIM", "error",
                f"'{eq.format_simple()}' writes var '{var.get_name()}' "
                f"(no dim {sorted(varying)}) but its RHS/condition "
                f"varies along {sorted(varying)} — every point of the "
                "missing extent would demand a different value for the "
                "single stored slab (intra-step race)",
                var=var.get_name(), dim=sorted(varying)[0],
                detail={"dims": sorted(varying)})
        # RACE-SAME-POINT: reading the value being computed this step
        # with no other equation to order against (analysis raises the
        # same condition when the dependency checker is enabled).
        vname = eq.lhs.var_name()
        if not var.is_scratch() and len(writers.get(vname, ())) == 1:
            for p in _reads_of(eq):
                if p.var_name() != vname:
                    continue
                if p.step_offset() == step_dir:
                    report.add(
                        "RACE-SAME-POINT", "error",
                        f"'{eq.format_simple()}' reads the value of "
                        f"'{vname}' it is writing in the same step "
                        "(intra-step race; the reference rejects this, "
                        "Eqs.cpp:364-470)", var=vname)
                    break

    # RACE-WAW-ORDER: several equations write the same var this step —
    # legal, with deterministic registration-order (last-write-wins)
    # semantics; surfaced so multi-writer solutions are a visible
    # choice, not an accident.
    for vname, ws in sorted(writers.items()):
        if len(ws) > 1:
            report.add(
                "RACE-WAW-ORDER", "info",
                f"{len(ws)} equations write var '{vname}' in one step; "
                "they execute in registration order (later writers "
                "win where conditions overlap)", var=vname,
                detail={"count": len(ws)})

    # RING-DEPTH: a manual set_step_alloc_size below what the step
    # accesses need silently drops a live time level.
    for v in soln.get_vars():
        manual = getattr(v, "_step_alloc", None)
        if manual is not None:
            need = v.min_step_alloc_size()
            if manual < need:
                report.add(
                    "RING-DEPTH", "error",
                    f"var '{v.get_name()}' has a manual step_alloc of "
                    f"{manual} but its step accesses need {need} "
                    "slots; a live time level would be evicted early",
                    var=v.get_name(),
                    detail={"manual": manual, "needed": need})

    # SCRATCH-HALO: the computed scratch write-halos must cover every
    # read demand (reader offset + the reader's own write-halo when it
    # writes scratch).  The analysis fixpoint guarantees this by
    # construction; the rule re-derives the demand independently so an
    # invariant drift (or a hand-mutated analysis) is caught instead of
    # silently under-computing the expanded region.
    ana = getattr(ctx, "_ana", None)
    swh = getattr(ana, "scratch_write_halo", None) if ana else None
    if swh is not None:
        for eq in eqs:
            lhs_var = eq.lhs.get_var()
            lhs_wh = swh.get(lhs_var.get_name())
            for p in _reads_of(eq):
                rv = p.get_var()
                if not rv.is_scratch():
                    continue
                wh = swh.get(rv.get_name(), {})
                for d, ofs in p.domain_offsets().items():
                    if d not in wh:
                        continue
                    base_l = base_r = 0
                    if lhs_wh is not None and d in lhs_wh:
                        base_l, base_r = lhs_wh[d]
                    need_l = base_l + max(0, -ofs)
                    need_r = base_r + max(0, ofs)
                    have_l, have_r = wh[d]
                    if have_l < need_l or have_r < need_r:
                        report.add(
                            "SCRATCH-HALO", "error",
                            f"scratch var '{rv.get_name()}' write-halo "
                            f"({have_l},{have_r}) in dim '{d}' does "
                            f"not cover the ({need_l},{need_r}) demand "
                            f"of '{eq.format_simple()}' — the expanded "
                            "in-tile region would read uncomputed "
                            "cells", var=rv.get_name(), dim=d,
                            detail={"have": [have_l, have_r],
                                    "need": [need_l, need_r]})

    # Analysis-level failures the equation scans cannot reproduce
    # (cycles, malformed LHS forms) arrive as the captured exception.
    if ana_error is not None:
        msg = str(ana_error)
        rule = ("RACE-CYCLE" if "circular dependency" in msg
                else "ANALYSIS-FAILED")
        already = ("intra-step race" in msg
                   and any(d.rule.startswith("RACE-")
                           for d in report.diagnostics))
        if not already:
            report.add(rule, "error", f"solution analysis failed: {msg}",
                       detail={"message": msg})


def check_distributed(report: CheckReport, ctx) -> None:
    """Static halo-sufficiency proofs for the sharded execution modes."""
    report.ran(PASS_DIST)
    mode = getattr(ctx, "_mode", None) or ctx._opts.mode
    if mode not in ("sharded", "shard_map", "shard_pallas"):
        report.add("DIST-SKIPPED", "info",
                   f"mode '{mode}' is single-device; no shard geometry "
                   "to prove")
        return
    opts = ctx._opts
    ana = ctx._ana
    dims = ana.domain_dims
    minor = dims[-1]
    nr = {d: opts.num_ranks[d] for d in dims}
    lsizes = opts.rank_domain_sizes
    K = max(opts.wf_steps, 1) if mode == "shard_pallas" else 1
    rad = ana.fused_step_radius()
    hK = {d: rad.get(d, 0) * K for d in dims}

    if mode in ("shard_map", "shard_pallas"):
        from yask_tpu.parallel.decomp import validate_shard_geometry
        from yask_tpu.utils.exceptions import YaskException
        try:
            validate_shard_geometry(ctx._csol, opts)
        except YaskException as e:
            report.add("DIST-GEOMETRY", "error",
                       f"shard geometry invalid: {e}",
                       detail={"message": str(e)})

    if mode == "shard_pallas" and K > 1 and nr.get(minor, 1) > 1:
        report.add(
            "DIST-MINOR-SHARD", "error",
            f"shard_pallas with wf_steps={K} > 1 cannot shard the "
            f"minor dim '{minor}' (its in-tile region never shrinks); "
            "use wf_steps 1 or keep the minor dim whole", dim=minor,
            detail={"wf_steps": K, "nr": nr.get(minor, 1)})

    for d in dims:
        if nr.get(d, 1) > 1 and hK[d] > 0 and lsizes[d] < hK[d]:
            report.add(
                "DIST-GHOST-PAD", "error",
                f"rank domain {lsizes[d]} in dim '{d}' is smaller than "
                f"the fused ghost width {hK[d]} (radius × wf_steps): "
                "one exchange cannot provide the halo the fused steps "
                "consume", dim=d,
                detail={"rank_domain": lsizes[d], "ghost": hK[d]})

    # Overlapped-exchange decision: replay the EXACT runtime gate
    # (shard_step.overlap_decision — one definition, so the checker and
    # the executor can never drift) statically.  Engage/auto-off are
    # informational; a forced "on" that the geometry cannot honor is
    # the error class _prep_shard_pallas would raise at build time.
    if mode == "shard_pallas":
        from yask_tpu.parallel.shard_step import overlap_decision
        setting = getattr(opts, "overlap_exchange", "auto")
        try:
            ov_ok, ov_core, ov_shells, ov_reasons = \
                overlap_decision(ctx, K)
        except Exception:
            ov_ok, ov_reasons = False, None  # geometry reported above
        if ov_ok:
            report.add(
                "OVERLAP-ENGAGED", "info",
                f"overlapped halo exchange engages (overlap_x="
                f"{setting}): core "
                f"{ {d: list(v) for d, v in sorted(ov_core.items())} } "
                "computes on pre-exchange state while the previous "
                f"group's collectives land; {len(ov_shells)} shell "
                "slab(s) of width radius×K patch the faces from the "
                "post-exchange state",
                detail={"core": {d: list(v)
                                 for d, v in sorted(ov_core.items())},
                        "shells": [[d, lo, hi]
                                   for d, lo, hi in ov_shells],
                        "setting": setting})
        elif ov_reasons is not None:
            why = "; ".join(r.get("cause", r.get("code", ""))
                            for r in ov_reasons)
            if setting == "on":
                report.add(
                    "OVERLAP-INFEASIBLE", "error",
                    f"overlap_x=on is forced but the core/shell split "
                    f"cannot engage: {why} — the build would raise; "
                    "use auto (falls back to the serial schedule) or "
                    "fix the geometry",
                    detail={"reasons": ov_reasons})
            else:
                report.add(
                    "OVERLAP-OFF", "info",
                    f"overlapped halo exchange stays off "
                    f"(overlap_x={setting}): {why}",
                    detail={"reasons": ov_reasons})

    # Communication schedule: the SAME CommPlan the executors consume
    # (ctx.comm_plan — one definition, checker and runtime cannot
    # drift).  Plan errors are the class run_shard_map/_prep_shard_pallas
    # raise at build time; order/coalesce decisions surface as info so a
    # sweep log records which schedule actually ran.
    if mode in ("shard_map", "shard_pallas"):
        try:
            plan = ctx.comm_plan(K)
        except Exception as e:  # plan construction itself must not kill
            plan = None
            report.add("COMM-PLAN", "warn",
                       f"comm plan construction failed: {e}",
                       detail={"message": str(e)})
        if plan is not None:
            for msg in plan.errors:
                report.add(
                    "COMM-ORDER", "error",
                    f"comm schedule invalid: {msg} — the build would "
                    "raise; fix -comm_order or leave it empty for the "
                    "cost-model ordering",
                    detail={"message": msg, "order": list(plan.order)})
            if not plan.errors:
                kinds = {a: plan.axes[a].get("kind", "ici")
                         for a in plan.order}
                # A DCN (cross-process) axis scheduled after an ICI axis
                # serializes the slow hop behind fast ones — only an
                # explicit -comm_order can produce this (auto sorts DCN
                # first).
                seen_ici = None
                for a in plan.order:
                    if kinds[a] == "ici":
                        seen_ici = a
                    elif kinds[a] == "dcn" and seen_ici is not None:
                        report.add(
                            "COMM-DCN-ORDER", "warn",
                            f"DCN axis '{a}' is ordered after ICI axis "
                            f"'{seen_ici}': the slowest link starts "
                            "last, so its latency cannot hide behind "
                            "the ICI rounds; put DCN axes first",
                            dim=a,
                            detail={"order": list(plan.order),
                                    "kinds": kinds})
                if plan.coalesce:
                    report.add(
                        "COMM-PLAN", "info",
                        f"comm schedule: order {list(plan.order)}, "
                        f"coalesced — {plan.rounds} collective round(s) "
                        f"per exchange vs {plan.rounds_serial} serial "
                        "(one ppermute per buffer slab)",
                        detail=plan.record())
                elif plan.order:
                    report.add(
                        "COMM-SERIAL", "info",
                        f"comm schedule: order {list(plan.order)}, "
                        "serial per-buffer collectives "
                        f"({plan.rounds_serial} per exchange; "
                        f"coalescing would issue {2 * len(plan.order)})",
                        detail=plan.record())

    # Distributed skew-margin proof: the stream dim, where the profit
    # gate would engage it (unsharded only), needs K·r left and r+E_sk
    # right inside the radius×K ghost pads — right-cover holds exactly
    # when E_sk ≤ (K−1)·r, which the gate implies; prove it anyway.
    if mode == "shard_pallas" and K > 1 and opts.skew_wavefront:
        from yask_tpu.ops.pallas_stencil import (skew_engaged_dims,
                                                 skew_extra_widths)
        try:
            local_prog = ctx._csol.plan(
                lsizes, global_sizes=opts.global_domain_sizes,
                extra_pad={d: (hK[d], hK[d]) for d in dims})
        except Exception:
            return  # geometry errors already reported above
        unsh = tuple(d for d in dims[:-1] if nr.get(d, 1) == 1)
        e_sk = skew_extra_widths(local_prog, K)
        for d in skew_engaged_dims(local_prog, K, unsharded=unsh):
            r = rad.get(d, 0)
            if r + e_sk.get(d, 0) > hK[d]:
                report.add(
                    "DIST-SKEW-MARGIN", "error",
                    f"skew dim '{d}': right margin r+E_sk = "
                    f"{r + e_sk.get(d, 0)} exceeds the ghost pad "
                    f"{hK[d]}; the carry would read unexchanged "
                    "cells", dim=d,
                    detail={"r": r, "E_sk": e_sk.get(d, 0),
                            "ghost": hK[d]})
            else:
                report.add(
                    "DIST-SKEW-COVERED", "info",
                    f"skew dim '{d}': margins K·r={hK[d]} (left), "
                    f"r+E_sk={r + e_sk.get(d, 0)} (right) are covered "
                    f"by the radius×K={hK[d]} ghost pads; the carry "
                    "never crosses a shard boundary", dim=d)
