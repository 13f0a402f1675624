"""yask_tpu.checker — static analysis over a configured solution.

Runs over a solution context + settings WITHOUT executing anything (no
state allocation, no kernel trace, no device work — planning is pure
geometry) and emits structured diagnostics.  Five passes:

* ``mosaic``      — the probed v5e TC legality rules (lane-128/
                    sublane-8 DMA alignment, misc-first physical order,
                    SMEM constraints, in-kernel pattern vocabulary);
* ``vmem``        — the static VMEM budget model per ladder rung,
                    including the live-value (register-spill) limit the
                    round-3 OOM violated;
* ``races``       — equation-level race rules (missing-dim, same-point,
                    WAW order, ring depth, scratch write-halo) plus the
                    distributed halo-sufficiency proofs;
* ``cache``       — persistent compile-cache hygiene (stale/corrupt
                    entry scan) and ensemble-batching feasibility for
                    the configured mode;
* ``ckpt``        — supervised-run configuration (checkpoint cadence vs
                    deadline budget, writable snapshot dir, fused
                    K-group alignment, restore-compat ladder proof);
* ``serve``       — server-hosted profile checks (micro-batching
                    compatibility of the configured mode, compile-cache
                    warmth for warm restart); gated on ``-serve``;
* ``pipeline``    — cross-solution pipeline fusion feasibility (fuse vs
                    host-chain, fused VMEM spill) from the same plan
                    dict the executor decides from; skipped for
                    contexts outside a pipeline;
* ``explain``     — every pallas/skew/pipelining decision and fallback
                    as a structured reason.

Entry points: :func:`run_checks` (library), ``python -m
yask_tpu.checker`` (CLI), :func:`preflight` (the gate a driver calls
before spending chip time on a statically-infeasible config: prints
findings, never raises).

See ``docs/checking.md`` for the rule catalog and JSON schema.
"""

from __future__ import annotations

import sys
from typing import Optional

from yask_tpu.checker.diagnostics import CheckReport, Diagnostic, SCHEMA
from yask_tpu.utils.exceptions import YaskException

__all__ = ["CheckReport", "Diagnostic", "SCHEMA", "run_checks",
           "preflight"]

PASSES = ("mosaic", "vmem", "races", "distributed", "cache", "ckpt",
          "serve", "pipeline", "explain")


def _dtype_name(dt) -> str:
    try:
        import numpy as np
        return np.dtype(dt).name if dt is not None else ""
    except Exception:
        return str(dt or "")


def run_checks(ctx, passes=None) -> CheckReport:
    """Run the static passes over a (prepared or unprepared) solution
    context.  Never allocates state: an unprepared context is planned
    through ``_plan_geometry()`` (pure geometry), so a 512³ feasibility
    question costs no memory.  Never raises for findings — everything
    becomes a diagnostic."""
    want = set(passes or PASSES)
    bad = want - set(PASSES)
    if bad:
        raise YaskException(f"unknown checker pass(es) {sorted(bad)}; "
                            f"available: {list(PASSES)}")

    program = getattr(ctx, "_program", None)
    plan_error: Optional[YaskException] = None
    if program is None:
        try:
            program = ctx._plan_geometry()
        except YaskException as e:
            plan_error = e

    from yask_tpu.backend import get_capability
    opts = ctx._opts
    report = CheckReport(config={
        "stencil": ctx.get_name(),
        "sizes": opts.global_domain_sizes.make_val_str("x"),
        "mode": getattr(ctx, "_mode", None) or opts.mode,
        "wf_steps": opts.wf_steps,
        "vmem_mb": opts.vmem_budget_mb or 0,
        "dtype": _dtype_name(getattr(ctx._csol, "dtype", None)),
        "backend": get_capability().name,
    })

    if plan_error is not None:
        msg = str(plan_error)
        if "cannot use the pallas" in msg or "cannot use the " in msg:
            report.add("PALLAS-APPLICABLE", "error", msg,
                       detail={"message": msg})
        else:
            report.add("PLAN-FAILED", "error",
                       f"geometry planning failed: {msg}",
                       detail={"message": msg})

    # races first: its rules hold at the yc level and do not need a
    # plan, so a plan failure never hides a race finding
    if "races" in want:
        from yask_tpu.checker.races import check_races
        ana_error = None
        if getattr(ctx, "_ana", None) is None:
            try:
                from yask_tpu.compiler.analysis import SolutionAnalysis
                SolutionAnalysis(ctx._csol.soln)
            except YaskException as e:
                ana_error = e
        check_races(report, ctx, ana_error=ana_error)
    if "distributed" in want:
        from yask_tpu.checker.races import check_distributed
        check_distributed(report, ctx)
    # cache pass needs no plan either: entry-metadata scan + the
    # ensemble feasibility mode property
    if "cache" in want:
        from yask_tpu.checker.cache_pass import check_cache
        check_cache(report, ctx)
    # ckpt pass is plan-free too: cadence/deadline/dir arithmetic over
    # the settings + the mode-degradation ladder
    if "ckpt" in want:
        from yask_tpu.checker.ckpt_pass import check_ckpt
        check_ckpt(report, ctx)
    # serve pass: batching feasibility + compile-cache warmth for a
    # server-hosted profile (gated on the -serve knob; plan-free)
    if "serve" in want:
        from yask_tpu.checker.serve_pass import check_serve
        check_serve(report, ctx)
    # pipeline pass: fuse/decline reproduction off the executor's own
    # plan dict (pipeline_plan does its own geometry planning; plan-free
    # here, and a no-pipeline context just gets a skip note)
    if "pipeline" in want:
        from yask_tpu.checker.pipeline_pass import check_pipeline
        check_pipeline(report, ctx)

    if program is not None:
        if "mosaic" in want:
            from yask_tpu.checker.mosaic import check_mosaic
            check_mosaic(report, ctx, program)
        if "vmem" in want:
            from yask_tpu.checker.vmem import check_vmem
            check_vmem(report, ctx, program)
        if "explain" in want:
            from yask_tpu.checker.explain import check_explain
            check_explain(report, ctx, program)

    return report


def preflight(ctx, out=None, verbose: bool = False) -> bool:
    """Driver-tool gate: run the checks, print errors/warnings, return
    whether the configuration is statically sound.  Honors the
    ``-preflight`` setting (returns True without checking when the
    user turned it off).  Never raises — a checker bug must not cost a
    chip run, so internal failures report True with a note."""
    out = out or sys.stderr
    if not getattr(ctx._opts, "preflight", True):
        return True
    try:
        report = run_checks(ctx)
    except Exception as e:  # never let the gate kill the launch path
        import traceback
        out.write(f"checker: internal failure ({type(e).__name__}: {e}); "
                  "skipping preflight\n")
        # the full traceback, so a swallowed checker bug is debuggable
        # from the session log instead of silently vanishing
        out.write(traceback.format_exc())
        # ...and a journal row, so a crashing pass is VISIBLE in the
        # session evidence instead of only scrolling past on stderr
        # (LOG-ONLY contract unchanged: the launch still proceeds)
        try:
            from yask_tpu.resilience.journal import (SessionJournal,
                                                     default_journal_path)
            SessionJournal(default_journal_path()).record(
                "preflight", case=ctx.get_name(),
                outcome="preflight_error",
                error_type=type(e).__name__, error=str(e)[:500])
        except Exception:
            pass  # the journal must never cost the launch either
        return True
    if report.errors or report.warnings or verbose:
        out.write(report.render(verbose=verbose))
    return report.ok()
