"""Runtime: seconds from the factory to a prepared solution -- the
kept spans ``yt.setup.env`` (``yk_factory.new_env``), ``yt.setup.solution``
(``new_solution``) and ``yt.setup.prepare`` (``prepare_solution``:
analysis, lowering, planning, mesh, the resting state's allocation),
and in a served cell ``yt.serve.open``, which does the last two inside
it.  ``None`` where the program keeps no record of its set-up."""

import program_setup


def read(run):
    return program_setup.read(run, "prepare_s")
