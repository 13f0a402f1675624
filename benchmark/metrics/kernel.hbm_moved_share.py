"""Kernels: the share of the chip's HBM peak at which the call's main
kernel moves the bytes its own DMAs move -- (``fetch_bytes_per_step``
+ ``write_bytes_per_step``) of its row in
``StencilContext.compiled_plans()`` (every grid step's input and output
copies of one launch on one device, over the steps it fuses) over the
peak's bytes a second, over the fused kernel's time a step.  Where
``kernel.hbm_need_share`` divides the bytes the algorithm needs (every
array once a group), this divides what the plan really fetches and
stores: halo rows, windows rounded out to the sublane tile, lanes that
hold no domain.  A kernel near 100 is on the HBM roof whatever its
need; the share cannot pass 100, since the bytes are the program's own
count of its DMAs and the time is the kernel's whole.  ``None`` where
the row lacks either key (a commit before ``write_bytes_per_step``),
there is no accessor (a served cell) or no fused kernel was traced."""

import program_plans
import program_spans


def share(row, hbm_bytes_per_s, fused_ms_per_step):
    """The percentage, from a plan row, the peak and the kernel's
    milliseconds a step; ``None`` without any of them."""
    if row is None or not hbm_bytes_per_s or not fused_ms_per_step:
        return None
    fetched = row.get("fetch_bytes_per_step")
    written = row.get("write_bytes_per_step")
    if fetched is None or written is None:
        return None
    least_s = (fetched + written) / hbm_bytes_per_s
    return 100.0 * least_s / (fused_ms_per_step / 1e3)


def read(run):
    if run.peak is None:
        return None
    return share(program_plans.main_plan(run),
                 run.peak["hbm_bytes_per_s"],
                 program_spans.load(run).get("fused_ms_per_step"))
