"""End to end: domain points x steps of all whole calls in the window
over the time from the first call's start to the last call's end
(upstream's fitness metric, ``context.cpp:449-460``)."""


def read(run):
    done = [u for u in run.units if u[2] > 0]
    if not done:
        return None
    span = run.units[-1][1] - run.units[0][0]
    return run.points * sum(u[2] for u in done) / span / 1e9
