"""Device: the longest idle interval of the traced window (the span
it fell in is printed with the breakdown)."""


def read(run):
    return run.trace.get("longest_gap_ms")
