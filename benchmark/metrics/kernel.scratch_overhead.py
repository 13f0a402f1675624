"""Kernels: points of scratch vars the call's main chunk evaluates
beyond the useful ones, per useful point of those vars --
``scratch_overhead`` of its row in ``StencilContext.compiled_plans()``.
A scratch var that is read with a halo (``tti``'s trig and rotated
derivative, read 4 away) is evaluated over the block grown by that
halo, the minor dim's too, at every grid step: 3.0625 = a little over
four scratch points evaluated a useful one (blocks 8x8 of 512 rows).
``kernel.margin_overhead`` counts one region a stage and reads 0.0 for
a chain inside one stage.  0.0 for a program without scratch vars.
From the plan, not from the trace; ``None`` where the program's rows
have no such key (an older commit) or there is no accessor (a served
cell)."""

import program_plans


def read(run):
    row = program_plans.main_plan(run)
    return None if row is None else row.get("scratch_overhead")
