"""Serving: seconds a request spends answering -- outputs pulled to
the host, sanity scan, journal: the program's ``yt.serve.respond``
spans (``serve/scheduler.py _release``) inside each traced
``yt.serve.request``, median over the requests."""

import program_spans


def read(run):
    return program_spans.load(run).get("serve_respond_s")
