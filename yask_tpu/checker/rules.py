"""The checker's rule-id registry — one declared catalog per pass.

Rule ids are a public, stable contract (``docs/checking.md``: "new
rules may be added, existing ids are never re-purposed"), but until
round 21 the ids only existed as string literals scattered across the
pass modules — nothing stopped a typo'd id, a silent rename, or a rule
that fired without a catalog row.  This module declares the full set,
and ``tests/test_checker_rules.py`` enforces the contract three ways:

* every literal ``report.add("RULE", ...)`` site in ``yask_tpu/
  checker/`` names a declared rule (AST scan — a typo cannot ship);
* the *dynamically constructed* ids are declared too: the
  ``vmem._classify_plan_error`` return set, the races pass's
  ``RACE-CYCLE``/``ANALYSIS-FAILED`` pair, and every planner reason
  code (scanned out of ``build_pallas_chunk``) mapped through
  ``explain._rule_of``;
* every declared rule has a row in ``docs/checking.md``.

Ids are unique across passes; the only sanctioned sharing is
:data:`CORE` (``PALLAS-APPLICABLE`` / ``PLAN-FAILED``), which both the
``run_checks`` entry itself and the mosaic/vmem passes may emit — a
plan failure is not owned by any one pass.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Tuple

#: emitted by ``run_checks`` itself (geometry-planning failures) and
#: re-usable by any pass that surfaces the same condition
CORE: Tuple[str, ...] = ("PALLAS-APPLICABLE", "PLAN-FAILED")

MOSAIC: Tuple[str, ...] = (
    "MOSAIC-SKIPPED", "MOSAIC-ALIGN-OFF", "MOSAIC-MISC-FIRST",
    "MOSAIC-SMEM", "MOSAIC-LANE-ALIGN", "MOSAIC-MINOR-DIM",
    "MOSAIC-SUBLANE-ALIGN", "MOSAIC-KERNEL-OPS",
)

#: includes the ``_classify_plan_error`` mapping targets — the planner
#: rejection classes are vmem-pass findings
VMEM: Tuple[str, ...] = (
    "VMEM-SKIPPED", "VMEM-OK", "VMEM-SPILL", "VMEM-SPILL-MARGIN",
    "VMEM-TILE-OVER-BUDGET", "VMEM-PIPE-OVER-BUDGET",
    "PALLAS-BLOCK-FIT", "PAD-COVERAGE", "SKEW-INFEASIBLE",
)

RACES: Tuple[str, ...] = (
    "RACE-MISSING-DIM", "RACE-SAME-POINT", "RACE-WAW-ORDER",
    "RING-DEPTH", "SCRATCH-HALO", "RACE-CYCLE", "ANALYSIS-FAILED",
)

DISTRIBUTED: Tuple[str, ...] = (
    "DIST-SKIPPED", "DIST-GEOMETRY", "DIST-MINOR-SHARD",
    "DIST-GHOST-PAD", "DIST-SKEW-MARGIN", "DIST-SKEW-COVERED",
    "OVERLAP-ENGAGED", "OVERLAP-INFEASIBLE", "OVERLAP-OFF",
    "COMM-PLAN", "COMM-ORDER", "COMM-DCN-ORDER", "COMM-SERIAL",
)

CACHE: Tuple[str, ...] = ("CACHE-STALE", "ENSEMBLE-INFEASIBLE")

CKPT: Tuple[str, ...] = ("CKPT-DIR", "CKPT-CADENCE", "CKPT-DEADLINE",
                         "CKPT-LADDER")

SERVE: Tuple[str, ...] = ("SERVE-BATCH-INCOMPAT",
                          "SERVE-BUCKET-INELIGIBLE", "SERVE-CACHE-COLD",
                          "SERVE-AUTOSCALE-BOUNDS")

PIPELINE: Tuple[str, ...] = ("PIPELINE-SKIPPED", "PIPELINE-INFEASIBLE",
                             "PIPELINE-VMEM-SPILL", "PIPELINE-ENGAGED",
                             "PIPELINE-PUSH-ENGAGED",
                             "PIPELINE-PUSH-INFEASIBLE",
                             "PIPELINE-PUSH-VMEM-SPILL")

#: every structured reason code ``build_pallas_chunk`` can record —
#: the explain pass republishes each as ``EXPLAIN-<CODE>``.  The
#: conformance test AST-scans the planner for ``{"code": ...}``
#: literals and fails on any code missing here (planner↔registry
#: drift check).
PLAN_REASON_CODES: Tuple[str, ...] = (
    "region_restricted",
    "skew_engaged", "skew_gate_rejected", "skew_ineligible",
    "skew_forced", "skew_disabled", "skew_fallback",
    "block_fitted", "block_shrunk", "block_overshoot",
    "pipe_in_on", "pipe_in_off", "pipe_out_on", "pipe_out_off",
    "push_engaged", "push_ineligible", "push_disabled", "push_forced",
    "eval_strip", "eval_tile", "fetch_whole",
    "scratch_hoisted", "scratch_hoist_kept",
)


def _explain_rules() -> Tuple[str, ...]:
    from yask_tpu.checker.explain import _rule_of
    fixed = ("EXPLAIN-MODE", "EXPLAIN-PALLAS-FALLBACK",
             "EXPLAIN-PLAN-FAILED", "EXPLAIN-TILING")
    return fixed + tuple(_rule_of(c) for c in PLAN_REASON_CODES)


def all_rules() -> Dict[str, Tuple[str, ...]]:
    """Pass name → declared rule ids (``core`` holds the shared
    entry-point rules)."""
    return {
        "core": CORE,
        "mosaic": MOSAIC,
        "vmem": VMEM,
        "races": RACES,
        "distributed": DISTRIBUTED,
        "cache": CACHE,
        "ckpt": CKPT,
        "serve": SERVE,
        "pipeline": PIPELINE,
        "explain": _explain_rules(),
    }


def flat_rules() -> FrozenSet[str]:
    """Every declared rule id, flattened."""
    out = set()
    for ids in all_rules().values():
        out.update(ids)
    return frozenset(out)
