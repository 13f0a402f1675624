"""Runtime: the largest ``memory_stats()["peak_bytes_in_use"]`` over
the cell's devices after the window, in GiB.  A loaded program's
reserved temporaries are not in it.  It moves by whole buffers with how
far the host ran ahead of the device, which is why it is no end-to-end
metric."""


def read(run):
    if not run.peak_bytes:
        return None
    return run.peak_bytes / 2 ** 30
