"""Device: 1 - union of device-operation intervals over the traced
window, busiest device."""


def read(run):
    return run.trace.get("idle_share")
