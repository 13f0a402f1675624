"""Kernels: the time the chip's HBM would need for the bytes the
algorithm must move (``stencils/<name>.py need_bytes_per_point_step``,
from shapes alone) over the kernel time per step.  Only the HBM side
of a roofline: v5e has no published VPU peak.  Points are one device's
share of the domain."""


def read(run):
    ms = run.trace.get("kernel_ms_per_step")
    if not ms or run.peak is None:
        return None
    points = run.points / run.trace["devices"]
    least_s = run.need_bytes_pp * points / run.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
