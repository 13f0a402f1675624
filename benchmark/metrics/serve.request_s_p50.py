"""Serving: median seconds of the window's whole requests, client
clock around ``srv.run``."""

import statistics


def read(run):
    secs = [b - a for a, b, steps in run.units if steps > 0]
    return statistics.median(secs) if secs else None
