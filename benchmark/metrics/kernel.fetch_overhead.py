"""Kernels: points the input tiles of the call's main chunk fetch
beyond the block's own, per point of the block, every DMA'd var and
ring slot counted -- ``fetch_overhead`` of its row in
``StencilContext.compiled_plans()`` (8.0 = nine points fetched for
each one the block owns: blocks of 8 with a halo of 8 either side).
The over-fetch that ``kernel.hbm_need_share`` cannot see: that one
divides the bytes the algorithm needs, not those the plan moves.  From
the plan, not from the trace; ``None`` where the program's rows have
no such key or there is no accessor (a served cell)."""

import program_plans


def read(run):
    row = program_plans.main_plan(run)
    return None if row is None else row.get("fetch_overhead")
