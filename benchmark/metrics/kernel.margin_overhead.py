"""Kernels: points the call's main chunk computes beyond the useful
ones, per useful point -- ``margin_overhead`` of its row in
``StencilContext.compiled_plans()`` (the plan the build ACTUALLY
chose: a kernel that fuses stages or steps recomputes its margins;
1.5 = two and a half points computed a useful point).  From the plan,
not from the trace; ``None`` where the program offers no such accessor
(an older commit, a served cell)."""

import program_plans


def read(run):
    row = program_plans.main_plan(run)
    return None if row is None else row["margin_overhead"]
