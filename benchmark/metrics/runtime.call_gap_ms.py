"""Runtime: median device-idle gap between consecutive kernel launches
inside one ``run_solution`` call, from the trace."""


def read(run):
    return run.trace.get("call_gap_ms")
