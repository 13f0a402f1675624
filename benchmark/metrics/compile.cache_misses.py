"""Compile: builds before the window that the backend compiled and a
warm run would have been spared -- the kept ``yt.cache.aot`` rows, at
any depth, whose ``hit`` is ``miss`` (not ``memory`` / ``disk``: the
program's own cache; not ``jax``: JAX's persistent cache served the
executable; not ``uncached``: compiled in less than that cache's
storing threshold, so every run pays it).  0 says the reading is a warm
one.  ``None`` where the program keeps no record of its set-up."""

import program_setup


def read(run):
    return program_setup.read(run, "cache_misses")
