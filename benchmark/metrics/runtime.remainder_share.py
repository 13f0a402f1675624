"""Runtime: the share of a ``run_solution`` call spent in the ``n mod
K`` steps that leave the fused kernel for the XLA path -- the program's
``yt.run.remainder`` span over its ``yt.run.call``
(``runtime/context.py _run_pallas_steps``), median over the traced
calls; 0 where a call has no remainder."""

import program_spans


def read(run):
    return program_spans.load(run).get("remainder_share")
