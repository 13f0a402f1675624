"""Kernels: points of the grid's blocks that lie past the domain's edge
in the lead dims, per point of the domain -- ``edge_overhead`` of the
main chunk's row in ``StencilContext.compiled_plans()``.  A grid
covers an extent that no block divides by rounding up (and a skewed
dim walks a few tiles more), so the last block of such a dim is
evaluated whole and the part beyond the edge is masked to zero:
(267 * 3 * 13 * 64) / 801^2 - 1 = 0.0387 for blocks 3 x 64 on 801 x
801.  From the plan, not from the trace; ``None`` where the program's
rows have no such key (an older commit) or there is no accessor (a
served cell)."""

import program_plans


def read(run):
    row = program_plans.main_plan(run)
    return None if row is None else row.get("edge_overhead")
