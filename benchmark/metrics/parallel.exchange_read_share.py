"""Parallel: of the bytes of edge slabs one interior shard sends, the
share that some equation of the step reads -- ``xbytes_read`` over
``xbytes`` of the program's ``yt.run.launch`` spans
(``parallel/shard_step.py _launch_attrs``: a slab counts as read where
the analysis has its var reading across that face in that direction),
each read the way ``parallel.exchange_mib_per_step`` reads ``xbytes``:
summed inside each whole traced ``yt.run.call``, median over the calls.
1.0 says nothing is sent that no read asked for; a schedule that sends
all nineteen populations of ``lbm_d3q19`` both ways across x, where
five cross a face each way, reads 0.26.  ``None`` where no launch
carries both attrs (a commit before ``xbytes_read``, a mode that
exchanges nothing)."""

from metric_alias import reader

_per_step = reader("parallel.exchange_mib_per_step")


def read(run):
    sent = _per_step(run)
    asked = _per_step(run, attr="xbytes_read")
    return None if not sent or asked is None else asked / sent
