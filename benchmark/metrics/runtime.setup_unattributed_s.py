"""Runtime: seconds of ``setup_s`` that are not the program's --
``run.setup_s`` less the top-level kept spans (``runtime.import_s`` +
``runtime.prepare_s`` + ``runtime.fill_s`` + ``compile.build_s``) and
less the warm-up units' time outside them: the process's start-up, the
harness's own imports and device search, ``device_state.py``'s seeding,
the probe reads between the warm-up units.  ``None`` where the program
keeps no record of its set-up."""

import program_setup


def read(run):
    return program_setup.read(run, "unattributed_s")
