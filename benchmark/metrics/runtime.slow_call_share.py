"""Runtime: the share of the window that stalled calls cost, in per
cent -- over the rows of ``StencilContext.call_log()`` whose call
started inside the window (all of it, not the traced units), the sum
of ``secs - median`` of the rows the program's own rule calls ``slow``
(more than 1.25 times the ``median`` of the up to 32 calls before it of
the same mode and length) over the sum of every row's ``secs``.  0.0 in
a steady window; one flagship call of 0.42 s among 147 of 0.27 reads
0.4.  ``None`` where the program keeps no such record (an older
commit, a served cell)."""

import program_calls


def read(run):
    rows = program_calls.window_rows(run)
    if not rows:
        return None
    lost = sum(r["secs"] - r["median"] for r in rows if r["slow"])
    return 100.0 * lost / sum(r["secs"] for r in rows)
