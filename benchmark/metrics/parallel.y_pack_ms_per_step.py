"""Parallel: device time of packing and unpacking the slabs that cross
mesh axis ``y`` (16 rows of the sublane dim cut out of every x row, not
one contiguous block), found by the program's per-axis
``jax.named_scope``s ``yt_exchange_pack_y`` / ``yt_exchange_unpack_y``
(``parallel/shard_step.py``) on the busiest device, over the steps
traced: a part of what ``parallel.pack_ms_per_step`` sums.  ``None``
where the trace holds neither label (a commit before the per-axis
scopes, a layout that leaves y whole).

A CPU dry run has no device plane and no module line, so
``program_spans.reduce`` labels nothing there; the host's XLA threads
that stand in for the device carry the instructions' names, and those
are joined to the scopes here, whatever the module: that the labels are
in the program, not a time."""

import os

import program_spans

LABELS = ("yt_exchange_pack_y", "yt_exchange_unpack_y")


def dry_run_labels(run) -> dict:
    """``{scope: ms}`` over the stand-in events of a ``--tiny`` run."""
    base = os.path.join(run.cell.scratch, "trace")
    paths = [os.path.join(d, f) for d, _s, files in os.walk(base)
             for f in files if f.endswith(".xplane.pb")]
    if not paths:
        return {}
    scope_of = {name: scope for table in program_spans.scope_map(
        program_spans.compiled_texts(run)).values()
        for name, scope in table.items()}
    out = {}
    for ops in program_spans.load_xplane(
            paths[0], dry_run=True)["devices"].values():
        for name, _start, dur, _kernel in ops:
            scope = scope_of.get(name.partition(" ")[0])
            if scope:
                out[scope] = out.get(scope, 0.0) + dur / 1e6
    return out


def read(run):
    spans = program_spans.load(run)
    by_label = (dry_run_labels(run) if run.cell.tiny
                else spans.get("by_label_ms") or {})
    found = [by_label[k] for k in LABELS if k in by_label]
    if not found or not spans.get("steps"):
        return None
    return sum(found) / spans["steps"]
