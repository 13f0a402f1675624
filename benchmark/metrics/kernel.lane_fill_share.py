"""Kernels: the share of the lanes every DMA and every vector
operation carries that hold domain -- ``lane_fill`` of the main
chunk's row in ``StencilContext.compiled_plans()``: the domain's minor
extent over the minor extent of the widest DMA'd tile (the halo either
side, then the pad to a multiple of 128 lanes), as a percentage.  187
of 256 = 73.05; the flagship's 640 of 768 = 83.33.  From the plan, not
from the trace; ``None`` where the program's rows have no such key (an
older commit) or there is no accessor (a served cell)."""

import program_plans


def read(run):
    row = program_plans.main_plan(run)
    fill = None if row is None else row.get("lane_fill")
    return None if fill is None else 100.0 * fill
