"""Device: the share of the busiest device's idle time (gaps of 2 ms
and more) that falls inside none of the program's ``yt.*`` spans other
than the roots (``yt.run.call``, ``yt.serve.request``): idle time the
program's own instrumentation does not account for."""

import program_spans


def read(run):
    return program_spans.load(run).get("idle_unspanned_share")
