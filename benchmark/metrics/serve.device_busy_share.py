"""Serving: device-busy time inside the traced request intervals over
the request time, from the trace."""


def read(run):
    return run.trace.get("request_busy_share")
