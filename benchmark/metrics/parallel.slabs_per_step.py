"""Parallel: edge slabs one interior shard sends per step -- the
``xslabs`` of the program's ``yt.run.launch`` spans, read the way
``parallel.exchange_mib_per_step`` reads their ``xbytes``."""

from metric_alias import reader

_per_step = reader("parallel.exchange_mib_per_step")


def read(run):
    return _per_step(run, attr="xslabs", unit=1)
