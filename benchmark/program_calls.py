"""The program's own record of its calls: the rows of
``StencilContext.call_log()`` (one a leaf ``run_solution`` call, kept
for the whole run and not for the traced units alone: ``t0`` on the
clock of ``run.units``, ``secs``, each launch's enqueue seconds, the
final wait, what the host did meanwhile, and the slow-call rule's
verdict ``slow`` against ``median``), for the per-layer readers that
count stalled calls over the whole window.  With a program that has no
such accessor (an older commit) or a cell whose kind holds no context
(served), there are no rows and every reader returns ``None``."""


def window_rows(run) -> list:
    """The rows of the calls that started inside the window."""
    log = getattr(getattr(run.cell.kind, "ctx", None), "call_log", None)
    if log is None or not run.units:
        return []
    start, end = run.units[0][0], run.units[-1][1]
    return [r for r in log() if start <= r["t0"] <= end]
