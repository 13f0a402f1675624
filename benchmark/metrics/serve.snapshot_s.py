"""Serving: seconds a request spends pulling the rollback snapshot to
the host -- the program's ``yt.serve.snapshot`` spans
(``serve/scheduler.py _execute_chunk``) inside each traced
``yt.serve.request``, median over the requests."""

import program_spans


def read(run):
    return program_spans.load(run).get("serve_snapshot_s")
