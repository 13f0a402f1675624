"""Parallel: summed device time of collective-permute operations per
step on the busiest device."""


def read(run):
    if not run.trace.get("has_collectives"):
        return None
    return run.trace["collective_ms_per_step"]
