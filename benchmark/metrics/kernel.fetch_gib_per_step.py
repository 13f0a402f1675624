"""Kernels: GiB the input DMAs of the call's main chunk move from HBM a
step on one device -- ``fetch_bytes_per_step`` of its row in
``StencilContext.compiled_plans()`` (every grid step's copies of one
launch, over the steps it fuses), in GiB.  Each (var, ring slot)
counted at the window the kernel's stages read of it, a slot no stage
reads at nothing: the bytes the plan moves, where
``kernel.hbm_need_share`` divides the bytes the algorithm needs.  From
the plan, not from the trace; ``None`` where the program's rows have no
such key (a commit before the fetch windows) or there is no accessor (a
served cell)."""

import program_plans


def read(run):
    row = program_plans.main_plan(run)
    moved = None if row is None else row.get("fetch_bytes_per_step")
    return None if moved is None else moved / 2 ** 30
