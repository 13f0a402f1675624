"""Parallel: the part of the collective time in which no kernel runs
on that device, over the traced window."""


def read(run):
    if not run.trace.get("has_collectives"):
        return None
    return run.trace["exposed_share"]
