"""Serving: seconds of a request inside the guarded run -- the
program's ``yt.serve.chunk`` spans (``serve/scheduler.py
_execute_chunk``) inside each traced ``yt.serve.request``, median over
the requests."""

import program_spans


def read(run):
    return program_spans.load(run).get("serve_run_s")
