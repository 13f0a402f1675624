"""Runtime: milliseconds the window's longest call took beyond the
window's median call -- over the rows of
``StencilContext.call_log()`` whose call started inside the window
(all of it, not the traced units).  A steady window reads a fraction of
a millisecond to a few; the rare stall reads hundreds or thousands.
``None`` where the program keeps no such record (an older commit, a
served cell)."""

import statistics

import program_calls


def read(run):
    secs = [r["secs"] for r in program_calls.window_rows(run)]
    if not secs:
        return None
    return 1e3 * (max(secs) - statistics.median(secs))
