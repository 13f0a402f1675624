"""Runtime: seconds of the program's own import -- the kept span
``yt.setup.import`` (``yask_tpu/__init__.py``, first line to last; jax
is imported by then where the harness has found its device first).
``None`` where the program keeps no record of its set-up (an older
commit)."""

import program_setup


def read(run):
    return program_setup.read(run, "import_s")
