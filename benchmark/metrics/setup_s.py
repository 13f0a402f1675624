"""End to end: process start to the window's start -- import, prepare,
fills, warm-up (compiling in a checkout's first run), probe reads."""


def read(run):
    return run.setup_s
