"""Kernels: what Mosaic's scoped VMEM holds for the call's main chunk
by the capability table's live-value model (``scoped_need_bytes`` of
its row in ``StencilContext.compiled_plans()``) over the 128 MiB a
kernel may ask for, in per cent.  The model's reading, not a
measurement: near 100 the plan sits at the edge of what Mosaic takes.
``None`` where the program offers no such accessor."""

import program_plans

SCOPED_CAP_BYTES = 128 * 2 ** 20    # capability.vmem_limit_cap_mib


def read(run):
    row = program_plans.main_plan(run)
    if row is None:
        return None
    return 100.0 * row["scoped_need_bytes"] / SCOPED_CAP_BYTES
