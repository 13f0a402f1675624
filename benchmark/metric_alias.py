"""A metric under a second name: BENCHMARK.json gives a per-layer
metric one end-to-end metric to move, so a quantity that served and
direct cells both report is registered twice (``<name>`` and
``<name>.serve``) with one reader."""

import os
import runpy


def reader(name: str):
    """The ``read`` of ``metrics/<name>.py``."""
    return runpy.run_path(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "metrics",
        name + ".py"))["read"]
