"""Run this directory's tests after the repository's older ones.

Tier-1 runs six workers on few cores with ``--dist loadfile``, and some
older tests read the wall clock (``test_runtime.py::
test_halo_time_measured`` calibrates a timing split and fails under
load).  Collected first, this directory's child processes shift which
files run beside which, and that test failed in two of two whole runs
(PR 23); moved to the end, the older files are scheduled as they were
before the directory existed.
"""


def pytest_collection_modifyitems(session, config, items):
    mine = [it for it in items if "tests/benchmark/" in it.nodeid]
    if mine and len(mine) < len(items):
        keep = set(map(id, mine))
        items[:] = [it for it in items if id(it) not in keep] + mine
