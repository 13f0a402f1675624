"""Parallel: MiB of edge slabs one interior shard sends per step, pads
included -- the ``xbytes`` of the program's ``yt.run.launch`` spans
(``parallel/shard_step.py _launch_attrs``: counted off the exchange
schedule that compiled, the up-front refresh of every slot and every
later round of the written fields' newest slots) summed inside each
whole traced ``yt.run.call``, over its ``n`` steps; median over the
calls.  ``None`` where no launch carries the attr (an older commit, a
mode that exchanges nothing)."""

import os

import program_spans


def host_spans(run) -> list:
    """The traced slice's ``yt.*`` / ``bench.*`` host events, parsed
    once a run (``program_spans.load`` keeps only what it reduced)."""
    if getattr(run, "host_spans", None) is None:
        base = os.path.join(run.cell.scratch, "trace")
        paths = [os.path.join(d, f) for d, _s, files in os.walk(base)
                 for f in files if f.endswith(".xplane.pb")]
        run.host_spans = (program_spans.load_xplane(
            paths[0], dry_run=run.cell.tiny)["spans"] if paths else [])
    return run.host_spans


def read(run, attr="xbytes", unit=2 ** 20):
    spans = host_spans(run)
    marks = [s for s in spans if s[0].startswith("bench.")] or spans
    if not marks:
        return None
    lo = min(s[1] for s in marks)
    hi = max(s[1] + s[2] for s in marks)
    per_step = []
    for call in spans:
        n = int(call[4].get("n", 0))
        if (call[0] != "yt.run.call" or n <= 0 or call[1] < lo
                or call[1] + call[2] > hi):
            continue
        sent = [int(s[4][attr]) for s in program_spans.within(
            spans, "yt.run.launch", call) if attr in s[4]]
        if sent:
            per_step.append(sum(sent) / n / unit)
    return program_spans.median(per_step)
