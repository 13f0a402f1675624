"""Explain pass: surface every planning decision as a diagnostic.

The pallas planner records a structured reason code at each decision
point (skew engage/reject per dim, each step of the 2-D → 1-D →
uniform fallback ladder, block shrinks, DMA-pipelining on/off) — see
``build_pallas_chunk``'s ``reasons`` parameter.  This pass replays the
planner in ``plan_only`` mode at the configured budget and republishes
those codes as ``EXPLAIN-*`` diagnostics: fallbacks are ``warn`` (the
kernel runs, but not the tiling that was asked for or modeled),
decisions are ``info``.  On the XLA modes it instead explains why the
pallas fast path is NOT in play (mode choice or applicability).
"""

from __future__ import annotations

from yask_tpu.checker.diagnostics import CheckReport
from yask_tpu.utils.exceptions import YaskException

PASS = "explain"

#: reason code → severity; everything else is info.
_SEVERITY = {
    "skew_fallback": "warn",
    "block_shrunk": "warn",
}


def _rule_of(code: str) -> str:
    return "EXPLAIN-" + code.upper().replace("_", "-")


def check_explain(report: CheckReport, ctx, program) -> None:
    report.ran(PASS)
    mode = ctx._mode
    if mode not in ("pallas", "shard_pallas"):
        from yask_tpu.ops.pallas_stencil import pallas_applicable
        ok, why = pallas_applicable(ctx._csol)
        if ok:
            report.add("EXPLAIN-MODE", "info",
                       f"mode '{mode}' selected; the pallas fused path "
                       "is applicable but not requested")
        else:
            report.add("EXPLAIN-PALLAS-FALLBACK", "info",
                       f"the pallas fused path cannot apply: {why}",
                       detail={"reason": why})
        return

    from yask_tpu.checker.vmem import checker_budget, plan_pallas
    try:
        plan = plan_pallas(ctx, program, checker_budget(ctx))
    except YaskException as e:
        # infeasibility itself is the vmem pass's finding; here it just
        # means there are no decisions to explain
        report.add("EXPLAIN-PLAN-FAILED", "info",
                   f"planner rejected the configuration ({e}); see the "
                   "vmem pass diagnostics")
        return

    for r in plan["reasons"]:
        code = r.get("code", "unknown")
        det = {k: v for k, v in r.items() if k != "code"}
        bits = []
        if "dim" in r:
            bits.append(f"dim '{r['dim']}'")
        if "cause" in r:
            bits.append(r["cause"])
        if "detail" in r:
            bits.append(str(r["detail"]))
        if code == "skew_fallback":
            bits.append(f"{r.get('from_dims')} -> {r.get('to')}")
        msg = code.replace("_", " ") + (": " + "; ".join(bits)
                                        if bits else "")
        report.add(_rule_of(code), _SEVERITY.get(code, "info"), msg,
                   dim=r.get("dim"), detail=det)

    report.add(
        "EXPLAIN-TILING", "info",
        f"final plan: K={plan['fuse_steps']}, block {plan['block']}, "
        f"grid {plan['grid']}, skew={plan['skew']} "
        f"{plan['skew_dims']}, "
        f"pipe_in={plan['pipeline_dmas']}, "
        f"pipe_out={plan['pipeline_out']}, tiles "
        f"{plan['tile_bytes'] / 2**20:.1f} MiB",
        detail={k: plan.get(k) for k in
                ("fuse_steps", "block", "grid", "skew", "skew_dims",
                 "pipeline_dmas", "pipeline_out", "tile_bytes")})
