"""Runtime: milliseconds the host spends enqueueing launches in one
``run_solution`` call -- the sum of the program's ``yt.run.launch``
spans (``runtime/context.py``, ``parallel/shard_step.py``) inside each
traced ``yt.run.call``, median over the calls."""

import program_spans


def read(run):
    return program_spans.load(run).get("enqueue_ms_per_call")
