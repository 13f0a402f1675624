"""Kernels: device time of the operations that carry one of the
program's kernel names (``yt_<solution>_r<radius>_k<K>...``,
``ops/pallas_stencil.py kernel_name``) on the busiest device, over the
steps the fused kernel advanced in the traced calls (the ``k`` of every
``yt.run.launch`` outside a ``yt.run.remainder``).  Steps an XLA
remainder ran are in neither."""

import program_spans


def read(run):
    return program_spans.load(run).get("fused_ms_per_step")
