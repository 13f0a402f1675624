"""Compile: seconds of the builds before the window, inside the
warm-up units or before them -- the kept spans ``yt.compile.chunk``
(the ``yt.cache.aot`` inside it counted once), ``yt.tuner.trial``,
``yt.halo_cal`` and any ``yt.cache.aot`` outside a chunk.  In a
checkout's first run the compiler's seconds; afterwards what a cache
hit still costs (tracing, lowering, deserialising: the span's
``lower_secs`` and ``load_secs``).  ``None`` where the program keeps no
record of its set-up."""

import program_setup


def read(run):
    return program_setup.read(run, "build_s")
