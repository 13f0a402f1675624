"""Parallel: device time of the XLA work the shard program does around
its kernels, found by the program's ``jax.named_scope``s
(``yt_exchange_pack`` / ``_unpack``, ``yt_shard_pad`` / ``_strip``,
``yt_shell_merge``, ``yt_zero_pads``: ``parallel/shard_step.py``,
``ops/pallas_stencil.py``) on the busiest device, over the steps
traced.  A scope is in no trace event: it is joined on from the
executables' HLO text (``program_spans.scope_map``); ``None`` where
the program offers none."""

import program_spans


def read(run):
    return program_spans.load(run).get("scoped_ms_per_step")
