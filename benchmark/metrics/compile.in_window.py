"""Compile: programs handed to the compiler during the window (JAX's
monitoring events; cached or not).  Must read 0: one more is set-up
that the warm-up missed."""


def read(run):
    return run.compiles_in_window
