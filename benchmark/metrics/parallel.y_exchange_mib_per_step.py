"""Parallel: MiB of edge slabs the busiest shard sends across mesh axis
``y`` (the sublane dim of the arrays) per step, pads included -- the
``xbytes_y`` of the program's ``yt.run.launch`` spans
(``parallel/shard_step.py _launch_attrs``: in a 2-wide axis a shard has
one neighbour, so one direction's slabs, where ``xbytes`` counts both),
read the way ``parallel.exchange_mib_per_step`` reads their ``xbytes``.
``None`` where no launch carries the attr (a commit before the per-axis
counts, a layout that leaves y whole)."""

from metric_alias import reader

_per_step = reader("parallel.exchange_mib_per_step")


def read(run):
    return _per_step(run, attr="xbytes_y")
