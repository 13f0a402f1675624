"""Kernels: the arithmetic the call's main kernel gets through a second,
in 1e9 operations -- ``dag_ops_per_point`` of its row in
``StencilContext.compiled_plans()`` (the operations of a point and step
with one that several equations share counted once, as the program
evaluates them: every stage's equations under the evaluation memo's
own scope) times one device's points, over the fused kernel's time a
step.  Beside ``kernel.hbm_moved_share`` it says on one line whether
bytes or arithmetic set the kernel's pace: a kernel far under the HBM
roof at a high rate here is held by its arithmetic.  A rate and not a
share: the benchmark has no vector-unit peak (``peaks.json``).  Loads,
stores, rotates and selects are not operations here, and the points a
tile computes beyond its block (``kernel.margin_overhead``) are not
counted either: useful operations only.  ``None`` where the row lacks
the key (a commit before it), there is no accessor (a served cell) or
no fused kernel was traced."""

import program_plans
import program_spans


def rate(row, points, fused_ms_per_step):
    """Gop/s from a plan row, the points of a step and the kernel's
    milliseconds a step; ``None`` without any of them."""
    if row is None or not fused_ms_per_step:
        return None
    ops = row.get("dag_ops_per_point")
    if ops is None:
        return None
    return ops * points / (fused_ms_per_step / 1e3) / 1e9


def read(run):
    return rate(program_plans.main_plan(run),
                run.points / run.trace["devices"],
                program_spans.load(run).get("fused_ms_per_step"))
