"""Parallel: device time of the shard path's shell kernels (the
program's names ending ``_shell``: the slabs that wait for the
exchange) on the busiest device, over the fused steps traced."""

import program_spans


def read(run):
    return program_spans.load(run).get("shell_ms_per_step")
