"""Kernels: summed device time of the Pallas custom calls on the
busiest device over the steps traced."""


def read(run):
    if not run.trace.get("kernel_ms_per_step"):
        return None
    return run.trace["kernel_ms_per_step"]
