"""Compile: host-clock seconds of the warm-up units, which compile in
a checkout's first run and load the persistent cache afterwards."""


def read(run):
    return sum(run.first_call_s)
