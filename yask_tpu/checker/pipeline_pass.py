"""Pipeline pass: plan-only fuse/decline reproduction for
cross-solution pipeline fusion (``yask_tpu.ops.pipeline``).

Reads the SAME plan dict the executor decides from
(:func:`yask_tpu.ops.pipeline.pipeline_plan` — one code path, the
checker cannot drift from the runtime) and renders it as diagnostics:

* ``PIPELINE-ENGAGED``    (info)  — the chain fuses into one program;
  detail carries the executor's decision (``fused``), the stage list,
  and the pallas plan summary when one was made;
* ``PIPELINE-INFEASIBLE`` (warn)  — one diagnostic per decline reason
  (structural ineligibility, no feasible pallas plan, failed merge
  prepare); warn, not error, because the pipeline still RUNS — it
  auto-falls back to the host-chained schedule;
* ``PIPELINE-VMEM-SPILL`` (error) — the merged chain's live-value
  model exceeds the Mosaic scoped limit (the round-3 register-spill
  OOM class): launching the fused arm would burn chip time on a
  doomed compile.

When the context is in a Pallas mode the plan is re-made at the
checker budget (the REAL-TPU default, never the CPU-interpret 100 MiB
— a CPU-host check must answer for Mosaic), so a laptop preflight
predicts the hardware verdict.
"""

from __future__ import annotations

from yask_tpu.checker.diagnostics import CheckReport
from yask_tpu.utils.exceptions import YaskException

PASS = "pipeline"


def check_pipeline(report: CheckReport, ctx) -> None:
    report.ran(PASS)
    pipe = getattr(ctx, "_pipeline", None)
    plan = getattr(ctx, "_pipeline_plan", None)
    if pipe is None and plan is None:
        report.add("PIPELINE-SKIPPED", "info",
                   "context is not part of a solution pipeline")
        return
    if pipe is not None:
        from yask_tpu.checker.vmem import checker_budget
        from yask_tpu.ops.pipeline import pipeline_plan
        try:
            plan = pipeline_plan(pipe, budget=checker_budget(ctx))
        except YaskException as e:
            report.add("PIPELINE-INFEASIBLE", "warn",
                       f"pipeline planning failed: {e}",
                       detail={"message": str(e)})
            return
    _render_plan(report, plan)


def check_pipeline_plan(pipe, budget=None) -> CheckReport:
    """Standalone helper: a CheckReport straight from a
    :class:`~yask_tpu.ops.pipeline.SolutionPipeline` (prepared or
    not), for callers without a context in hand — e.g. a structurally
    ineligible pipe that never built a fused context."""
    from yask_tpu.ops.pipeline import pipeline_plan
    report = CheckReport(config={"pipeline": pipe.name,
                                 "stages": list(pipe.stage_names)})
    report.ran(PASS)
    if pipe._merged is None:
        plan = {"fused": False, "eligible": False, "sig": pipe.signature(),
                "stages": list(pipe.stage_names), "mode": None,
                "reasons": [dict(r) for r in pipe._struct_reasons]}
    else:
        plan = pipeline_plan(pipe, budget=budget)
    _render_plan(report, plan)
    return report


def _render_plan(report: CheckReport, plan) -> None:
    for r in plan.get("reasons", ()):
        code = r.get("code")
        det = {k: v for k, v in r.items() if k not in ("msg",)}
        if r.get("ok"):
            # push decisions are worth surfacing even when ok: engaged
            # means stale rings (the caller should know), ineligible
            # explains why the HBM halving did not happen
            if code == "pipeline-push-engaged":
                report.add("PIPELINE-PUSH-ENGAGED", "info", r["msg"],
                           detail=det)
            elif code == "pipeline-push-ineligible":
                report.add("PIPELINE-PUSH-INFEASIBLE", "info", r["msg"],
                           detail=det)
            continue
        if code == "pipeline-vmem-spill":
            report.add("PIPELINE-VMEM-SPILL", "error", r["msg"],
                       detail=det)
        elif code == "pipeline-push-vmem-spill":
            report.add("PIPELINE-PUSH-VMEM-SPILL", "error", r["msg"],
                       detail=det)
        else:
            report.add("PIPELINE-INFEASIBLE", "warn",
                       f"[{r['code']}] {r['msg']}",
                       detail=det)
    if plan.get("fused"):
        det = {"fused": True, "sig": plan.get("sig"),
               "stages": plan.get("stages"),
               "mode": plan.get("mode")}
        if "pallas" in plan:
            det["pallas"] = plan["pallas"]
        if "hbm_model" in plan:
            det["hbm_model"] = plan["hbm_model"]
        report.add("PIPELINE-ENGAGED", "info",
                   f"{len(plan.get('stages', ()))}-stage chain fuses "
                   f"into one {plan.get('mode')} program "
                   f"(sig {plan.get('sig')})", detail=det)
    else:
        report.add("PIPELINE-ENGAGED", "info",
                   "pipeline runs the host-chained schedule "
                   "(fused=False)",
                   detail={"fused": False, "sig": plan.get("sig")})
