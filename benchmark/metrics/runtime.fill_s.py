"""Runtime: seconds the state took onto the device through the
program -- the kept spans ``yt.state.fill`` (a public fill of
``runtime/var.py``), ``yt.serve.set_var`` (a session's upload, the fill
inside it counted once), ``yt.state.to_device`` and ``yt.state.derive``,
before the window.  The one-chip ``advance`` cells seed their fields
through ``device_state.py``, past the public API: there it reads the
scalars' fills and what a first call pushes or derives, and the seeding
lies in ``runtime.setup_unattributed_s``.  ``None`` where the program
keeps no record of its set-up."""

import program_setup


def read(run):
    return program_setup.read(run, "fill_s")
